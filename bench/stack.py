"""The system under test, built as ``serve.py --gateway-port 0`` builds it.

The stack is the program's own: :class:`AsyncStreamEngine` with its
defaults (vmap step, ``fused=None``, no deadline tracker, governor or
supervisor) and a :class:`MetricsRegistry`, behind a
:class:`repro.serving.gateway.Gateway` on an ephemeral loopback port. No
option is passed that the served path does not pass. The data the program
serves, the concept codes of the item memory and the bank of task weights,
is made here from the seed, on the device in one jitted call; the program
derives its item-memory views from the codes.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np


def torr_config(cfg: dict):
    from repro.core.types import TorrConfig

    return TorrConfig(**cfg["torr"])


def make_data(cfg: dict, seed: int):
    """``(codes int8 [M, D] ±1, task bank f32 [T, M])`` on the device."""
    import jax
    import jax.numpy as jnp

    t = cfg["torr"]
    shape = (t["M"], t["D"])
    n_tasks = cfg["tasks"]

    @jax.jit
    def make(key):
        k_codes, k_task = jax.random.split(key)
        codes = jnp.where(jax.random.bernoulli(k_codes, 0.5, shape),
                          jnp.int8(1), jnp.int8(-1))
        task = jax.random.uniform(k_task, (n_tasks, t["M"]), jnp.float32,
                                  0.25, 1.0)
        return codes, task

    key_bits = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return make(jax.random.PRNGKey(int(key_bits)))


@dataclasses.dataclass
class Stack:
    cfg: Any          # TorrConfig
    eng: Any          # AsyncStreamEngine
    gw: Any           # Gateway
    registry: Any     # MetricsRegistry

    def close(self) -> None:
        self.gw.drain(timeout=30.0)
        self.gw.close()
        self.eng.close(drain=False)


def build(cfg: dict, codes, task_bank, plan=None) -> Stack:
    """Build, warm and start the stack (not yet listening). ``plan`` latches
    a program ``KnobPlan`` before the warm-up: the control's reduced
    precision, never used by a benchmark run."""
    import jax

    from repro.core.item_memory import build_item_memory
    from repro.obs import MetricsRegistry
    from repro.serving.async_engine import AsyncStreamEngine
    from repro.serving.gateway import Gateway, GatewayLimits

    tcfg = torr_config(cfg)
    im = jax.jit(build_item_memory, static_argnames="plane_total")(
        codes, plane_total=tcfg.bit_planes)
    registry = MetricsRegistry()
    eng = AsyncStreamEngine(tcfg, im, n_slots=cfg["slots"], paused=True,
                            metrics=registry)
    if plan is not None:
        eng.set_plan(plan)
    eng.warmup()
    eng.start()
    g = cfg["gateway"]
    limits = GatewayLimits(
        rate_per_s=g["rate_per_s"], burst=g["burst"],
        request_deadline_s=g["request_deadline_ms"] / 1e3,
        max_connections=g["max_connections"],
        max_sessions_per_tenant=g["max_sessions_per_tenant"])
    gw = Gateway(eng, tcfg, np.asarray(task_bank), limits=limits,
                 host="127.0.0.1", port=0, metrics=registry)
    return Stack(cfg=tcfg, eng=eng, gw=gw, registry=registry)
