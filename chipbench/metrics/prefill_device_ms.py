"""Model step, prefill: device time of one execution of ``Server``'s
jitted ``prefill`` (one request's prompt, in a full-batch pass), in ms,
averaged over the traced calls.  Moves ``ttft_p90_ms``."""

from chipbench import trace as T


def read(ctx):
    runs = T.runs(ctx["trace"], "jit_prefill")
    return 1e3 * T.mean([e - s for s, e in runs]) if runs else None
