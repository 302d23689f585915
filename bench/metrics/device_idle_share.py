"""device_idle_share (device): the share of the traced slice in which no
operation ran on the device, 1 - busy / window, from the profiler trace.
In the closed-loop cells."""


def read(ctx):
    if ctx.trace is None or not ctx.trace["window_s"]:
        return None
    return (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"]) * 100.0
