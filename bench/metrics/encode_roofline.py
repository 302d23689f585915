"""encode_roofline (kernels): the roofline time of the encode work the
windows needed over the device time of the ``sign_project_pack`` kernel
in the traced slice.

The work of q = sign(R z) is counted from ``torr_encoded_rows_total``
(the valid feature rows the steps encoded) and ``torr_steps_total``:
2 D d int8 operations a row; bytes R once a step (D d int8), d int8 bytes
in and D / 8 packed bytes out a row. The kernel encodes every row of a
step, valid or not, so the share reads what the windows needed against
what the step spent. The roofline time is the larger of operations over
the int8 peak and bytes over the HBM bandwidth (``bench/work.py``); which
of the two bounds it goes into the traced result line under
``roofline_bound``. The kernel is matched by name in the trace's device
ops. A program with no such counter or kernel has nothing to read.
"""

KERNEL = "sign_project_pack"


def ops(D: int, d: int, rows: float) -> float:
    """int8 operations of encoding ``rows`` feature rows."""
    return 2.0 * D * d * rows


def encode_bytes(D: int, d: int, rows: float, steps: float) -> float:
    """Compulsory HBM bytes: R once a step, each row's features in and its
    packed words out."""
    return steps * D * d + rows * (d + D / 8.0)


def read(ctx):
    if ctx.peak is None or ctx.trace is None:
        return None
    kernel_s = sum(s for name, s in ctx.trace["op_s"].items()
                   if KERNEL in name)
    rows = ctx.counter("torr_encoded_rows_total")
    steps = ctx.counter("torr_steps_total")
    if not kernel_s or not rows:
        return None
    D, d = ctx.cfg["D"], ctx.spec.config["features"]["dim"]
    t, bound = ctx.work.roofline_s(ops(D, d, rows),
                                   encode_bytes(D, d, rows, steps), ctx.peak)
    ctx.roofline_bound["encode_roofline"] = bound
    return t / kernel_s * 100.0
