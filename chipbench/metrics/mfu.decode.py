"""Model step, decode: the whole decode step's share of the chip's peak
bf16 FLOP/s, in %: the traced ticks' flops over their device time.
Moves ``tpot_p90_ms``."""

from chipbench import flops
from chipbench import trace as T


def read(ctx):
    runs = T.runs(ctx["trace"], "jit_decode")
    kv = ctx["decode_kv"]
    if not runs or len(runs) != len(kv):
        return None
    work = sum(flops.decode(ctx["config"], ctx["slots"], n)[0] for n in kv)
    return 100.0 * work / sum(e - s for s, e in runs) / ctx["peaks"]["flops_bf16"]
