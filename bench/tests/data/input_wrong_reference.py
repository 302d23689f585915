"""The default input module with its reference planted wrong, for the
harness's tests: one score of every window is off by one, so every
window compared must count as a wrong answer."""
from __future__ import annotations

from bench.inputs import packed
from bench.inputs.packed import answer, build, fields, make_data, windows

__all__ = ["answer", "build", "fields", "make_data", "reference", "windows"]


def reference(config: dict, data: dict, stream: int):
    replay = packed.reference(config, data, stream)

    def window(w):
        scores, paths = replay(w)
        scores = scores.copy()
        scores[0, 0] += 1.0
        return scores, paths
    return window
