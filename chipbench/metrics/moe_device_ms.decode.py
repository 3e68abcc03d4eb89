"""MoE layer, decode: leaf-op device time of the MoE layers (the ``moe``
scope: the norm, router, top-k and dispatch sort, the combine, the shared
expert; and the held experts' grouped matmuls with the copies of their
weights that they read, which the compiler leaves outside the scope), in
ms per traced decode tick.  Moves ``tpot_p90_ms``."""

from chipbench import moe_scope


def read(ctx):
    got = moe_scope.decode_seconds(ctx)
    return 1e3 * got[0] / got[1] if got else None
