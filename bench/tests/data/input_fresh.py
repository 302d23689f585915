"""An input module of its own, for the harness's tests: a configuration
that names it is served and checked through it alone.

Its windows are fresh random queries under a valid prefix that grows by a
row a window and wraps; its data are codes and a task bank from its own
key; its stack is the shared one; its reference is the window FSM over
that data. The tests copy it into a checkout as a new file.
"""
from __future__ import annotations

import numpy as np

from bench import reference as ref
from bench.wire import encode

answer = ref.answer


def windows(traffic: dict, config: dict, seed: int, stream: int):
    t = config["torr"]
    rng = np.random.default_rng([int(seed), int(stream), 99])
    n_max, words = t["N_max"], t["D"] // 32
    boxes = np.zeros((n_max, 4), np.float32)
    k = 0
    while True:
        q = rng.integers(0, 1 << 32, (n_max, words), dtype=np.uint32)
        yield q, np.arange(n_max) <= k % n_max, boxes
        k += 1


def fields(window) -> dict:
    q, valid, boxes = window
    return {"q": encode(q), "valid": encode(valid), "boxes": encode(boxes)}


def make_data(config: dict, seed: int) -> dict:
    import jax
    import jax.numpy as jnp

    t = config["torr"]

    @jax.jit
    def make(key):
        k_codes, k_task = jax.random.split(key)
        codes = jnp.where(jax.random.bernoulli(k_codes, 0.5, (t["M"], t["D"])),
                          jnp.int8(1), jnp.int8(-1))
        task = jax.random.uniform(k_task, (config["tasks"], t["M"]),
                                  jnp.float32, 0.5, 1.0)
        return {"codes": codes, "task_bank": task}
    return make(jax.random.PRNGKey(int(seed) % 2**31 + 1))


def build(config: dict, data: dict, plan=None):
    from bench import stack

    return stack.build(config, data["codes"], data["task_bank"], plan=plan)


def reference(config: dict, data: dict, stream: int):
    replay = ref.Stream(config["torr"], data["codes"],
                        data["task_bank"][stream % config["tasks"]])
    return lambda window: replay.window(window[0], window[1])
