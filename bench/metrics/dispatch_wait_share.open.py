"""dispatch_wait_share.open (engine host): the share of the traced slice
in which the async engine's dispatcher waited for backlog, in the open-loop
cells: the sum of ``torr_span_duration_seconds{span="dispatch_wait"}``
over the slice (waits that ended in it) over the slice's seconds. The
dispatcher enters the span only when it has no window to take. Without the
span's series (a program that does not record it) there is nothing to
read."""


def read(ctx):
    fam = ctx.snaps[1].get("torr_span_duration_seconds") or {"series": []}
    if not any(s["labels"].get("span") == "dispatch_wait"
               for s in fam["series"]):
        return None
    total, _n = ctx.hist("torr_span_duration_seconds", span="dispatch_wait")
    return total / ctx.span_s * 100.0
