"""step_mfu (step): the int8 operations that the windows served in the
traced slice needed (``bench/work.py``: 2 M D' per full-path proposal,
2 M per Eq. 6 dimension, from ``torr_path_total`` and
``torr_delta_dims_total``), over the slice's host-clock seconds times the chip's int8
peak. Read only on a chip in ``bench/peaks.json``. D' is taken as the
whole D, as in ``scan_roofline``: Alg. 1 keeps all banks in every cell
here (no governor, queue depth 0)."""


def read(ctx):
    if ctx.peak is None or ctx.trace is None:
        return None
    n_ops = ctx.work.ops(ctx.cfg["M"], ctx.cfg["D"],
                         ctx.counter("torr_path_total", path="full"),
                         ctx.counter("torr_delta_dims_total"))
    if not n_ops:
        return None
    return n_ops / (ctx.span_s * ctx.peak["int8_ops_per_s"]) \
        * 100.0
