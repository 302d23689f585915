"""scan_roofline (kernels): the full-path scan's roofline time over the
device time of the scan kernels in the traced slice.

The work is what the windows needed (``bench/work.py``), read from
``torr_path_total{path="full"}`` and the step count: 2 M D' int8
operations per full-path proposal; the packed item memory read once per
step, each full-path query once and one f32 score row per full-path
proposal. The roofline time is the larger of operations over the int8 peak
and bytes over the HBM bandwidth; which of the two bounds it goes into
the traced result line under ``roofline_bound``. The kernels are matched
by name in the trace's device ops.

D' is taken as the whole D: Alg. 1 keeps all B banks while the engine
runs with no governor and a queue depth of 0, as every cell here does. A
cell in which it may choose fewer banks needs a reader of the banks it
chose, or this one overstates the work.
"""

# device op names of the full-path XNOR-popcount scan
SCAN_KERNELS = ("bank_prefix_hamming",)


def read(ctx):
    if ctx.peak is None or ctx.trace is None:
        return None
    kernel_s = sum(s for name, s in ctx.trace["op_s"].items()
                   if any(k in name for k in SCAN_KERNELS))
    n_full = ctx.counter("torr_path_total", path="full")
    steps = ctx.counter("torr_steps_total")
    if not kernel_s or not n_full:
        return None
    M, d_eff = ctx.cfg["M"], ctx.cfg["D"]
    t, bound = ctx.work.roofline_s(
        ctx.work.ops(M, d_eff, n_full, 0),
        ctx.work.scan_bytes(M, d_eff, n_full, steps), ctx.peak)
    ctx.roofline_bound["scan_roofline"] = bound
    return t / kernel_s * 100.0
