"""windows_per_s: windows answered 200 within the measured window, divided
by its seconds."""


def read(ctx):
    return ctx.answered / ctx.seconds
