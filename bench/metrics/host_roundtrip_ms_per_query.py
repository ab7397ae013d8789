"""The unfused consume's round trip per query: the program's ``to_host``
and ``to_device`` spans (each decoded column copied to the host and back)
inside the window, summed, over the window's completed queries, in ms."""

from bench import span_reduce


def read(run):
    return span_reduce.per_query_ms(run, {"to_host", "to_device"})
