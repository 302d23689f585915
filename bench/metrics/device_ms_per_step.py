"""device_ms_per_step (step): device busy time in the traced slice (the
union of the device's op intervals, from the profiler trace) per engine
step (``torr_steps_total`` over the same slice)."""


def read(ctx):
    steps = ctx.counter("torr_steps_total")
    if ctx.trace is None or not steps:
        return None
    return ctx.trace["busy_s"] / steps * 1e3
