"""client_lag_ms (load generator): the 95th percentile of how late the
clients sent the windows scheduled in the measured window, actual send
minus scheduled send, in an open loop. A stream sends its windows one
after another on one connection, so a window due before its stream's last
reply is in is sent late by that wait; one never sent counts with its age
at the close."""

import numpy as np


def read(ctx):
    if ctx.loop != "open":
        return None
    lag = [(r[3] - r[2]) * 1e3 for r in ctx.records
           if ctx.t_open <= r[2] < ctx.t_close]
    lag += [(ctx.t_close - t_sched) * 1e3 for _s, t_sched in ctx.unsent]
    return float(np.percentile(lag, 95)) if lag else None
