"""Device: share of the traced window, in %, in which no operation ran on
the device.  Moves ``tokens_per_s``."""

from chipbench import trace as T


def read(ctx):
    tr = ctx["trace"]
    lo, hi = tr.window
    if hi <= lo or T.busy_seconds(tr) <= 0:
        return None
    return 100.0 * (1.0 - T.busy_seconds(tr) / (hi - lo))
