"""full_path_share (query cache): the share of valid proposals that took
the full path over the traced slice, from ``torr_path_total{path}``."""


def read(ctx):
    n = {p: ctx.counter("torr_path_total", path=p)
         for p in ("bypass", "delta", "full")}
    total = sum(n.values())
    return n["full"] / total * 100.0 if total else None
