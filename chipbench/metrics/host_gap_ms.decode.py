"""Server host loop: mean device-idle gap, in ms, between two back-to-back
executions of the decode program.  Per-slot sampling on the host and the
next dispatch fill it.  Moves ``tpot_p90_ms``."""

from chipbench import trace as T


def read(ctx):
    gaps = T.consecutive_gaps(ctx["trace"], "jit_decode")
    return 1e3 * T.mean(gaps) if gaps else None
