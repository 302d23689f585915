"""phased_device_share (step): the share of the device's op time in the
traced slice that the program's phase table names.

The program publishes which ops of its compiled step belong to which named
phase (``torr_step_phase_ops{phase, ops}``, see
``delta_device_ms_per_step``). This reader sums the device self time
(``op_s`` of the trace reduction) of the ops the table names, matched by
HLO instruction name, and divides by the self time of every device op in
the slice. It guards the phase metrics: an op that loses its scope, or a
step the table does not describe, lowers it. Without the phase table there
is nothing to read.
"""


def phase_of_op(snap) -> dict:
    """``{instruction name: phase}`` from a registry snapshot."""
    fam = snap.get("torr_step_phase_ops") or {"series": []}
    return {op: s["labels"]["phase"] for s in fam["series"]
            for op in s["labels"]["ops"].split()}


def read(ctx):
    table = phase_of_op(ctx.snaps[1])
    if ctx.trace is None or not table:
        return None
    op_s = ctx.trace["op_s"]
    total = sum(op_s.values())
    if not total:
        return None
    # a trace op is named "%fusion.190 fusion": instruction, then opcode
    named = sum(s for name, s in op_s.items()
                if name.split(" ")[0].lstrip("%") in table)
    return named / total * 100.0
