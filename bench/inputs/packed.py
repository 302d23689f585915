"""Packed query hypervectors in: the default input module (see
``bench/inputs/__init__.py``).

Each window is ``(q uint32 [N_max, D/32], valid bool [N_max], boxes f32
[N_max, 4])`` from the traffic generator (``bench/traffic.py``); the data
is the concept codes and the task bank (``bench/stack.py``); the reference
is the numpy window FSM (``bench/reference.py``).
"""
from __future__ import annotations

import numpy as np

from bench import reference as ref
from bench import traffic as tr
from bench.wire import encode

answer = ref.answer


def windows(traffic: dict, config: dict, seed: int, stream: int):
    t = config["torr"]
    gen = tr.StreamGen(traffic, t["N_max"], t["D"], seed, stream)
    while True:
        yield gen.next()


def fields(window) -> dict:
    q, valid, boxes = window
    return {"q": encode(q), "valid": encode(valid), "boxes": encode(boxes)}


def make_data(config: dict, seed: int) -> dict:
    from bench import stack

    codes, task_bank = stack.make_data(config, seed)
    return {"codes": codes, "task_bank": task_bank}


def build(config: dict, data: dict, plan=None):
    from bench import stack

    return stack.build(config, data["codes"], data["task_bank"], plan=plan)


def reference(config: dict, data: dict, stream: int):
    codes = data["codes"]
    replay = ref.Stream(config["torr"], codes,
                        data["task_bank"][stream % config["tasks"]],
                        codes.astype(np.float32))
    return lambda window: replay.window(window[0], window[1])
