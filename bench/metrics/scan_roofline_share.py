"""The least time the chip's HBM could take to read what the window's
queries must read (the stored bytes of every page their zone maps cannot
exclude, from the footers, over the peak bandwidth in bench/peaks.json),
over the summed time of the device operations in the trace, in %."""


def read(run):
    t = run.trace
    if t is None or not run.records or t["op_s"] <= 0:
        return None
    need = sum(run.required_bytes[r.query] for r in run.records)
    return need / run.peaks["hbm_bytes_per_s"] / t["op_s"] * 100.0
