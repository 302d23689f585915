"""setup_s: seconds from JAX's devices in hand to the open of the measured
window: data from the seed, the stack's build and warm-up (its compiles,
or their loads from the persistent cache), the client's spawn and every
stream's warm windows. The process's own start, JAX's import and the
chip's bring-up come before it and are not counted."""


def read(ctx):
    return ctx.setup_s
