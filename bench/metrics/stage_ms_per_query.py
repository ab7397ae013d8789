"""Host-to-device staging per query: the program's ``stage`` spans (the
transfer of each decode group's packed arrays and dictionaries) inside the
window, summed, over the window's completed queries, in ms."""

from bench import span_reduce


def read(run):
    return span_reduce.per_query_ms(run, {"stage"})
