"""Model step, decode: share of the roofline, in %.  For each traced tick
the least time the chip could take, the larger of its flops over peak
FLOP/s and its necessary bytes (every weight once, each slot's cache up
to its filled length) over peak bandwidth, summed and divided by the
ticks' device time.  Moves ``tpot_p90_ms``."""

from chipbench import flops
from chipbench import trace as T


def read(ctx):
    runs = T.runs(ctx["trace"], "jit_decode")
    kv = ctx["decode_kv"]
    if not runs or len(runs) != len(kv):
        return None
    peak = ctx["peaks"]
    least = 0.0
    for n in kv:
        f, b = flops.decode(ctx["config"], ctx["slots"], n)
        least += max(f / peak["flops_bf16"], b / peak["hbm_bytes_per_s"])
    return 100.0 * least / sum(e - s for s, e in runs)
