"""Effective read bandwidth: logical bytes of the columns each completed
query reads (rows x physical width, from the file footers), summed over
the window's queries, per second of the window, in GB/s."""


def read(run):
    total = sum(run.logical_bytes[r.query] for r in run.records)
    return total / run.window_s / 1e9 if run.records else None
