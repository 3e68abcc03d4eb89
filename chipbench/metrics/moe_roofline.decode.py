"""MoE layer, decode: share of its roofline, in %.  The least time of a
tick's MoE layers, the larger of their flops (router, the held choices,
the shared expert) over peak FLOP/s and their bytes (router, every held
expert and the shared expert, once) over peak bandwidth
(``moe_decode`` of the family's costs), over the device time of the
MoE layers per traced tick (``moe_device_ms.decode``'s).  Moves
``tpot_p90_ms``."""

from chipbench import cells, moe_scope


def read(ctx):
    got = moe_scope.decode_seconds(ctx)
    if not got:
        return None
    seconds, ticks = got
    config, peak = ctx["config"], ctx["peaks"]
    costs = cells.load_module(cells.HERE / "costs" / f"{config['family']}.py")
    f, b = costs.moe_decode(config, ctx["slots"])
    least = max(f / peak["flops_bf16"], b / peak["hbm_bytes_per_s"])
    return 100.0 * ticks * least / seconds
