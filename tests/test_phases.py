"""Named device phases: every lowering of the served step names its phases,
and the phase table maps the compiled step's ops to them.

The table is read from the optimized HLO of an executable compiled here on
the CPU at toy widths; the same code reads the TPU's (``obs/phases.py``).
"""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import pipeline
from repro.core.item_memory import random_item_memory
from repro.core.types import StreamBatch
from repro.obs.phases import PHASES, phase, phase_of, phase_table

from test_multistream import CFG

S = 4
_STEP = jax.jit(pipeline.torr_stream_batch_step,
                static_argnames=("cfg", "serial", "plan", "fused",
                                 "bucket_cap", "decide"))
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ", re.M)


def _compiled_text(**kw) -> str:
    im = random_item_memory(jax.random.PRNGKey(0), CFG)
    state = pipeline.init_multi_stream_state(
        CFG, jnp.zeros((S, CFG.M), jnp.float32))
    batch = StreamBatch(
        q_packed=jnp.zeros((S, CFG.N_max, CFG.words), jnp.uint32),
        valid=jnp.zeros((S, CFG.N_max), bool),
        boxes=jnp.zeros((S, CFG.N_max, 4), jnp.float32),
        queue_depth=jnp.zeros((S,), jnp.int32))
    return _STEP.lower(state, im, batch, CFG, **kw).compile().as_text()


def _fused_instructions(text) -> set:
    """Instructions inside fused computations: they run as part of their
    fusion, which is the op."""
    fused = set(re.findall(r" fusion\(.*?calls=%?([\w.\-]+)", text))
    out = set()
    for block in re.split(r"\n(?=\S)", text):   # a computation per block
        m = re.match(r"%?([\w.\-]+) \(", block)
        if m and m.group(1) in fused:
            out |= set(_INSTRUCTION.findall(block))
    return out


@pytest.mark.parametrize("kw", [
    {},                                          # the served prefix step
    {"fused": "compact", "decide": "batched"},
    {"fused": "compact", "decide": "scan"},
])
def test_every_phase_of_the_step_is_in_its_table(kw):
    text = _compiled_text(**kw)
    table = phase_table(text)
    assert set(table.values()) == set(PHASES)
    assert set(table) <= set(_INSTRUCTION.findall(text))
    assert not set(table) & _fused_instructions(text)


def test_switch_lowering_has_no_prefix_scan():
    table = phase_table(_compiled_text(serial=True))
    assert "prefix_scan" not in table.values()
    assert {"policy", "full_select", "delta_search",
            "finish"} <= set(table.values())


def test_hlo_without_a_scope_maps_to_nothing():
    text = """HloModule jit_f, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%fused_computation (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %add.1 = f32[4]{0} add(%param_0, %param_0), metadata={op_name="jit(f)/delta_search/add"}
}

ENTRY %main.3 (x.1: f32[4]) -> f32[4] {
  %x.1 = f32[4]{0} parameter(0), metadata={op_name="x"}
  %mul.2 = f32[4]{0} multiply(%x.1, %x.1), metadata={op_name="jit(f)/mul"}
  ROOT %fusion = f32[4]{0} fusion(%mul.2), kind=kLoop, calls=%fused_computation
}
"""
    assert phase_table(text) == {}
    # the fused add carries a scope, but runs inside the fusion: the
    # fusion itself, named for its root, is the op
    text = text.replace("calls=%fused_computation",
                        'calls=%fused_computation, metadata={op_name='
                        '"jit(f)/delta_search/add"}')
    assert phase_table(text) == {"fusion": "delta_search"}


def test_innermost_phase_wins_and_transforms_unwrap():
    assert phase_of("jit(f)/vmap(policy)/jit(_where)/select_n") == "policy"
    assert phase_of("jit(f)/while/body/cache_write/reason/top_k") == "reason"
    assert phase_of("jit(f)/while/body/closed_call/gather") is None
    assert phase_of("") is None
    with pytest.raises(ValueError, match="unknown step phase"):
        phase("decide")
