"""Front-end queue wait per query: the program's ``queued`` spans (submit
to run, and any admission wait in the scan service) inside the window,
summed, over the window's completed queries, in ms."""

from bench import span_reduce


def read(run):
    return span_reduce.per_query_ms(run, {"queued"})
