"""The exact DECIMAL reduce per query: the program's ``exact_sum`` spans
(each row group's scaled-integer Q6 reduce: dispatch, the wait for its
block sums and their combine on the host) inside the window, summed, over
the window's completed queries, in ms; 0 in a cell whose Q6 runs no exact
reduce.  None for a program that has no exact reduce (no ``q6_decimal``
in ``repro.core.query``), as it records no such span."""

from bench import span_reduce


def read(run):
    from repro.core import query
    if not hasattr(query, "q6_decimal"):
        return None
    return span_reduce.per_query_ms(run, {"exact_sum"})
