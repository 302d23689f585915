"""queue_wait_ms (engine host): the mean wait of a served window in the
engine's queue, from its submit to the dispatch of the step that takes it:
the engine's ``torr_window_queue_seconds`` sum over its count, across the
traced slice (windows dispatched in it). Without the histogram (a program
that does not keep it) there is nothing to read."""


def read(ctx):
    total, n = ctx.hist("torr_window_queue_seconds")
    return total / n * 1e3 if n else None
