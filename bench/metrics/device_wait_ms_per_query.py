"""Host time blocked on the device per query: the program's
``device_wait`` spans (decode's flush, each consumed column, Q6's partial
sums, Q12's counts) inside the window, summed, over the window's completed
queries, in ms."""

from bench import span_reduce


def read(run):
    return span_reduce.per_query_ms(run, {"device_wait"})
