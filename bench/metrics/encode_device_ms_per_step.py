"""encode_device_ms_per_step (step): device time of the served step's
``encode`` phase (q = sign(R z) of feature windows, bit-packed) per engine
step.

As ``delta_device_ms_per_step`` reads its phases: the program publishes
which ops of its compiled step belong to which named phase
(``torr_step_phase_ops{phase, ops}``); this reader sums the device self
time (``op_s`` of the trace reduction) of the ops in the ``encode`` phase,
matched by HLO instruction name, over the traced slice, and divides by
``torr_steps_total`` over the same slice. A step with no ``encode`` op
(packed windows, or a program without the phase) has nothing to read.
"""


def phase_of_op(snap) -> dict:
    """``{instruction name: phase}`` from a registry snapshot."""
    fam = snap.get("torr_step_phase_ops") or {"series": []}
    return {op: s["labels"]["phase"] for s in fam["series"]
            for op in s["labels"]["ops"].split()}


def read(ctx):
    encode = {op for op, ph in phase_of_op(ctx.snaps[1]).items()
              if ph == "encode"}
    steps = ctx.counter("torr_steps_total")
    if ctx.trace is None or not encode or not steps:
        return None
    # a trace op is named "%fusion.190 fusion": instruction, then opcode
    encode_s = sum(s for name, s in ctx.trace["op_s"].items()
                   if name.split(" ")[0].lstrip("%") in encode)
    return encode_s / steps * 1e3
