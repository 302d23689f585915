"""p95_window_ms: the 95th percentile latency over every window of the
measured window (closed loop: from the send to the reply, of the windows
answered in it; open loop: from the scheduled send to the reply, or the
age at the close of a window still unanswered)."""

import numpy as np


def read(ctx):
    if not ctx.latencies_ms:
        return None
    return float(np.percentile(ctx.latencies_ms, 95))
