"""Share of the traced window in which no operation ran on the device:
1 - (union of device-operation intervals) / window, averaged over the
chips, in %."""


def read(run):
    t = run.trace
    if t is None:
        return None
    return (1.0 - t["busy_s"] / t["window_s"]) * 100.0
