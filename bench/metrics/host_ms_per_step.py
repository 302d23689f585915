"""host_ms_per_step (engine host): the engine's host spans over the traced
slice (``torr_span_duration_seconds`` of host_decide, host_assemble,
dispatch_enqueue, collector_drain, host_observe), summed, per engine step
(``torr_steps_total``). In the closed-loop cells."""

SPANS = ("host_decide", "host_assemble", "dispatch_enqueue",
         "collector_drain", "host_observe")


def read(ctx):
    steps = ctx.counter("torr_steps_total")
    if not steps:
        return None
    total = sum(ctx.hist("torr_span_duration_seconds", span=s)[0]
                for s in SPANS)
    return total / steps * 1e3
