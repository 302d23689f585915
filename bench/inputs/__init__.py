"""Input modules: what a configuration's clients send, how its stack is
built and served, and how its plain reference replays the windows.

A configuration file may name its input module by a path from the checkout
root, ``"input": "bench/inputs/<name>.py"``; one that names none gets
:data:`DEFAULT`, packed query hypervectors. So a deployment that sends a
different window comes in with new files only. A module is loaded by its
path, like a metric reader, and provides three groups of functions:

Client side, numpy only (``bench/client.py`` imports the module and never
imports JAX; so the module imports JAX only inside its server side):

``windows(traffic, config, seed, stream)``
    an iterator over stream ``stream``'s windows, in order; window t
    depends only on ``(traffic, config, seed, stream, t)``.
``fields(window) -> dict``
    what the window adds to the ``POST /v1/window`` body.

Server side:

``make_data(config, seed) -> dict``
    the data the program serves, made from the seed on the device in one
    jitted call: ``{name: array}``.
``build(config, data, plan=None) -> bench.stack.Stack``
    the stack built from that data, warmed and started, not yet listening;
    ``plan`` latches a program ``KnobPlan`` (the control only).

Check side, importing nothing of the program (it runs in worker processes,
with the data as numpy arrays):

``reference(config, data, stream)``
    a callable ``window -> (scores f32 [N_max, M], paths)`` that answers
    the stream's windows in order; ``paths`` counts the valid proposals
    that took bypass, delta and full.
``answer(scores) -> (best, digest)``
    what the gateway replies for those scores.
"""
from __future__ import annotations

import importlib.util
import os
import re
import sys

DEFAULT = "bench/inputs/packed.py"


def path_of(root: str, config: dict) -> str:
    """The input module's file of a configuration in a checkout."""
    return os.path.join(root, config.get("input", DEFAULT))


def load(path: str):
    """The input module at ``path``."""
    name = "bench_input_" + re.sub(r"\W", "_", os.path.abspath(path))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod
