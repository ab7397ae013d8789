"""Host-clock decode time per query: the program's
ScanMetrics.decode_seconds (inflate, pack, stage, launch) summed over a
query's scan reports, mean over the window's queries, in ms."""


def read(run):
    if not run.records:
        return None
    total = sum(rep.metrics.decode_seconds for r in run.records
                for rep in r.reports)
    return total / len(run.records) * 1e3
