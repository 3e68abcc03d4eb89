"""Model step, prefill: useful flops of each traced call (its request's
own prompt, one row, causal attention, the last position unembedded)
over the calls' device time and the chip's peak bf16 FLOP/s, in %.
Moves ``ttft_p90_ms``."""

from chipbench import flops
from chipbench import trace as T


def read(ctx):
    runs = T.runs(ctx["trace"], "jit_prefill")
    plens = ctx["prefill_plens"]
    if not runs or len(runs) != len(plens):
        return None
    work = sum(flops.prefill(ctx["config"], n) for n in plens)
    return 100.0 * work / sum(e - s for s, e in runs) / ctx["peaks"]["flops_bf16"]
