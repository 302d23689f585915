"""device_idle_share.open (device): ``device_idle_share`` in the open-loop
cells, where it moves the tail: the share of the traced slice in which no
operation ran on the device, 1 - busy / window, from the profiler trace."""


def read(ctx):
    if ctx.trace is None or not ctx.trace["window_s"]:
        return None
    return (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"]) * 100.0
