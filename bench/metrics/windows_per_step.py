"""windows_per_step (engine host): windows served per engine step over the
traced slice, ``torr_windows_total`` / ``torr_steps_total``: how full the
16 slots of a step are."""


def read(ctx):
    steps = ctx.counter("torr_steps_total")
    return ctx.counter("torr_windows_total") / steps if steps else None
