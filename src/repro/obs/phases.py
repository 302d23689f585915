"""Named device phases of the served step, and the table that maps the
compiled step's ops to them.

The step (``core.pipeline``) wraps each of its phases in a
``jax.named_scope`` from one fixed vocabulary, :data:`PHASES`. A scope
changes only metadata: every HLO instruction the phase lowers to carries
the scope as a component of its ``op_name``, in the compiled program too,
so the program can name its own device ops.

:func:`phase_table` reads an executable's optimized HLO text
(``Compiled.as_text()``) and maps each instruction that runs as an op of
its own to its phase: the instructions of the entry computation, of while
bodies and conditions, of conditional branches and of called
computations, but not those inside a fusion, a reducer or a comparator,
which run as part of another op. These are the ops a profiler shows on
the device. An op's phase is the innermost vocabulary component of its
``op_name``; an op with none is left out.

The engines build the table once, from the executable their warm-up
compiled, and publish it as the gauge family ``torr_step_phase_ops``
(:meth:`repro.obs.bridge.StepObserver.publish_phases`): one series per
phase, ``ops`` holding the space-separated instruction names and the value
their count. A profile's per-op device time then sums by phase.
"""
from __future__ import annotations

import re

import jax

#: the served step's phases, in the order the step runs them
PHASES = (
    "prefix_scan",   # the hoisted bank-prefix XNOR-popcount kernel
    "policy",        # Alg. 1's bank and high-load choice, the word mask
    "full_select",   # the full path's accumulators from the scan
    "nearest",       # the query cache's nearest entry (PSU lookup)
    "delta_search",  # the flipped dims between a query and its entry
    "path_select",   # Alg. 1's bypass / delta / full decision
    "delta_apply",   # Eq. 6 corrections and their readout
    "reason",        # readout and task gating of the full path
    "cache_write",   # the query cache's touch, write and LRU choice
    "finish",        # the window's telemetry and outputs
)
_PHASE_SET = frozenset(PHASES)

# ops that never run on the device: they name values, not work
_NOT_RUN = frozenset(("parameter", "constant", "get-tuple-element", "tuple",
                      "bitcast"))

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*? ([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_TRANSFORM = re.compile(r"^\w+\((.*)\)$")
# attributes that name a computation run as ops of its own
_RUN_CALLS = re.compile(
    r"\b(?:body|condition|true_computation|false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")


def phase(name: str):
    """The named scope of one step phase; ``name`` must be in
    :data:`PHASES`."""
    if name not in _PHASE_SET:
        raise ValueError(f"unknown step phase {name!r}; phases: {PHASES}")
    return jax.named_scope(name)


def phase_of(op_name: str):
    """The innermost :data:`PHASES` component of an ``op_name``, or None.
    A component may carry JAX's transform around the scope, as
    ``vmap(policy)`` does for a scope entered first under ``vmap``."""
    for part in reversed(op_name.split("/")):
        while (m := _TRANSFORM.match(part)) is not None:
            part = m.group(1)
        if part in _PHASE_SET:
            return part
    return None


def _computations(hlo_text: str):
    """``({computation: [instruction line]}, entry name)``."""
    comps, entry, lines = {}, None, None
    for line in hlo_text.splitlines():
        if lines is not None and line.startswith("}"):
            lines = None
            continue
        if lines is None:
            m = _COMPUTATION.match(line)
            if m and line.rstrip().endswith("{"):
                lines = comps.setdefault(m.group(1), [])
                if line.startswith("ENTRY"):
                    entry = m.group(1)
            continue
        lines.append(line)
    return comps, entry


def phase_table(hlo_text: str) -> dict:
    """``{instruction name: phase}`` over the ops of an executable's
    optimized HLO text that run on their own (see the module docstring)."""
    comps, entry = _computations(hlo_text)
    table, todo, seen = {}, [entry], {entry}
    while todo:
        for line in comps.get(todo.pop(), ()):
            m = _INSTRUCTION.match(line)
            if m is None:
                continue
            name, opcode = m.groups()
            called = _RUN_CALLS.findall(line)
            if opcode == "call":
                called += _CALLS.findall(line)
            for group in _BRANCHES.findall(line):
                called += [c.strip().lstrip("%") for c in group.split(",")]
            for c in called:
                if c not in seen:
                    seen.add(c)
                    todo.append(c)
            if opcode in _NOT_RUN:
                continue
            op = _OP_NAME.search(line)
            ph = phase_of(op.group(1)) if op else None
            if ph is not None:
                table[name] = ph
    return table
