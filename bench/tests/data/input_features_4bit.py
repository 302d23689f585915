"""The features input module with a planted fault on the server side, for
the harness's tests and a control on the chip: the engine quantizes every
feature row to 4 bits (multiples of 16) before it encodes, while the
reference encodes the features as sent, so the bits near the projection's
hyperplanes, and with them the answers, differ."""
from __future__ import annotations

import numpy as np

from bench.inputs import features
from bench.inputs.features import (answer, fields, make_data, reference,
                                   windows)

__all__ = ["answer", "build", "fields", "make_data", "reference", "windows"]


def build(config: dict, data: dict, plan=None):
    stack = features.build(config, data, plan=plan)
    submit = stack.eng.submit

    def submit_4bit(stream_id, z, valid, boxes):
        z4 = np.clip(np.rint(np.asarray(z, np.float32) / 16), -8, 7) * 16
        return submit(stream_id, z4.astype(np.int8), valid, boxes)
    stack.eng.submit = submit_4bit
    return stack
