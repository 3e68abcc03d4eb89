"""Model step, decode: device time of one execution of ``Server``'s
jitted ``decode``, in ms, averaged over the traced ticks.  Moves
``tpot_p90_ms``."""

from chipbench import trace as T


def read(ctx):
    runs = T.runs(ctx["trace"], "jit_decode")
    return 1e3 * T.mean([e - s for s, e in runs]) if runs else None
