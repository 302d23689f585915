"""delta_device_ms_per_step (step): device time of the served step's delta
phases per engine step.

The program names the phases of its step (``jax.named_scope``) and
publishes which ops of the compiled step belong to which phase, as the
gauge family ``torr_step_phase_ops{phase, ops}`` (``ops``: the
space-separated HLO instruction names). This reader sums the device self
time (``op_s`` of the trace reduction) of the ops in the ``delta_search``
and ``delta_apply`` phases, matched by instruction name, over the traced
slice, and divides by ``torr_steps_total`` over the same slice. Without
the phase table (a program that does not publish it) there is nothing to
read.
"""

DELTA_PHASES = ("delta_search", "delta_apply")


def phase_of_op(snap) -> dict:
    """``{instruction name: phase}`` from a registry snapshot."""
    fam = snap.get("torr_step_phase_ops") or {"series": []}
    return {op: s["labels"]["phase"] for s in fam["series"]
            for op in s["labels"]["ops"].split()}


def read(ctx):
    table = phase_of_op(ctx.snaps[1])
    steps = ctx.counter("torr_steps_total")
    if ctx.trace is None or not table or not steps:
        return None
    # a trace op is named "%fusion.190 fusion": instruction, then opcode
    delta_s = sum(s for name, s in ctx.trace["op_s"].items()
                  if table.get(name.split(" ")[0].lstrip("%"))
                  in DELTA_PHASES)
    return delta_s / steps * 1e3
