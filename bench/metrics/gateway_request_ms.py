"""gateway_request_ms (gateway): the mean time from a window request's
receipt to its response written, from the gateway's
``torr_gateway_request_seconds{route="window"}`` sum and count over the
traced slice."""


def read(ctx):
    total, n = ctx.hist("torr_gateway_request_seconds", route="window")
    return total / n * 1e3 if n else None
