"""Decoder-stack composition: blocks, layer layouts, scan-over-layers.

A *block* = mixer (attention / MLA / mamba) + FFN (dense MLP / MoE / none),
pre-norm residual.  An architecture is a *layout*: a list of BlockKinds.
Layouts compress into *segments* — (pattern, repeats) pairs — and each
segment becomes one ``jax.lax.scan`` over stacked parameters:

    granite-8b    [(attn+mlp,) x 36]            -> 1 segment, scan 36
    deepseek-v3   [(mla+mlp,) x 3, (mla+moe,) x 58] -> 2 segments
    mamba2        [(mamba+none,) x 64]           -> 1 segment
    jamba         [(8-layer hybrid pattern) x 4]  -> 1 segment of period 8

Scanning keeps the compiled HLO O(1) in depth — essential for lowering
61-layer 671B-parameter modules for 512 devices on a CPU host.

Caches thread through scan as per-segment stacked pytrees (leading dim =
repeats).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from . import attention as attn_mod
from . import mamba as mamba_mod
from . import moe as moe_mod
from .attention import AttnConfig, MLAConfig
from .layers import layernorm, layernorm_defs, rmsnorm, rmsnorm_defs, swiglu, swiglu_defs
from .mamba import SSMConfig
from .moe import MoEConfig
from .params import ParamDef, stack_defs


@dataclasses.dataclass(frozen=True)
class BlockKind:
    mixer: str  # 'attn' | 'mla' | 'mamba'
    ffn: str  # 'mlp' | 'moe' | 'none'

    def tag(self) -> str:
        return f"{self.mixer}_{self.ffn}"


@dataclasses.dataclass(frozen=True)
class StackConfig:
    """Everything the decoder stack needs (built by ModelConfig)."""

    d_model: int
    d_ff: int
    layout: Tuple[BlockKind, ...]
    mlp_kind: str = "swiglu"  # 'swiglu' | 'gelu'
    attn: Optional[AttnConfig] = None
    mla: Optional[MLAConfig] = None
    ssm: Optional[SSMConfig] = None
    moe: Optional[MoEConfig] = None
    norm: str = "rmsnorm"  # 'rmsnorm' | 'layernorm'
    norm_eps: float = 1e-6
    remat: str = "none"  # 'none' | 'full'
    # granite's scale of each sublayer's output before the residual add
    residual_multiplier: Optional[float] = None
    # optional activation-sharding constraint applied to the residual
    # stream at every block boundary (the launcher installs e.g. a
    # sequence-parallel (batch, seq-over-model, none) constraint here)
    act_constraint: Any = None


# ---------------------------------------------------------------------------
# layout segmentation
# ---------------------------------------------------------------------------


def segments(layout: Sequence[BlockKind]) -> List[Tuple[Tuple[BlockKind, ...], int]]:
    """Compress a layout into (pattern, repeats) segments.

    First tries whole-layout periodicity (jamba); falls back to maximal
    runs of identical kinds (deepseek prefix).  Lossless:
    sum(len(p)*r) == len(layout).
    """
    n = len(layout)
    # whole-layout period (smallest p dividing n with layout = pattern*k, k>1)
    for p in range(1, n // 2 + 1):
        if n % p:
            continue
        pattern = tuple(layout[:p])
        if all(layout[i] == pattern[i % p] for i in range(n)):
            if n // p > 1 and len(set(pattern)) > 1 or p == 1:
                return [(pattern, n // p)]
    # maximal identical runs
    segs: List[Tuple[Tuple[BlockKind, ...], int]] = []
    i = 0
    while i < n:
        j = i
        while j < n and layout[j] == layout[i]:
            j += 1
        segs.append(((layout[i],), j - i))
        i = j
    return segs


# ---------------------------------------------------------------------------
# one block
# ---------------------------------------------------------------------------


def _norm_defs(cfg: StackConfig) -> Dict[str, ParamDef]:
    return (
        layernorm_defs(cfg.d_model) if cfg.norm == "layernorm" else rmsnorm_defs(cfg.d_model)
    )


def _norm(cfg: StackConfig, params, x):
    if cfg.norm == "layernorm":
        return layernorm(params, x, cfg.norm_eps)
    return rmsnorm(params, x, cfg.norm_eps)


def block_defs(cfg: StackConfig, kind: BlockKind) -> Dict[str, Any]:
    defs: Dict[str, Any] = {"norm_mixer": _norm_defs(cfg)}
    if kind.mixer == "attn":
        defs["attn"] = attn_mod.attn_defs(cfg.attn)
    elif kind.mixer == "mla":
        defs["mla"] = attn_mod.mla_defs(cfg.mla)
    elif kind.mixer == "mamba":
        defs["mamba"] = mamba_mod.mamba_defs(cfg.ssm)
    else:
        raise ValueError(kind.mixer)
    if kind.ffn == "mlp":
        defs["norm_ffn"] = _norm_defs(cfg)
        from .layers import gelu_mlp_defs

        defs["mlp"] = (
            gelu_mlp_defs(cfg.d_model, cfg.d_ff)
            if cfg.mlp_kind == "gelu"
            else swiglu_defs(cfg.d_model, cfg.d_ff)
        )
    elif kind.ffn == "moe":
        defs["norm_ffn"] = _norm_defs(cfg)
        defs["moe"] = moe_mod.moe_defs(cfg.moe)
    elif kind.ffn != "none":
        raise ValueError(kind.ffn)
    return defs


# the named scope of each mixer kind in a profile
MIXER_SCOPES = {"attn": "attn", "mla": "mla", "mamba": "ssm"}


def _residual(cfg: StackConfig, x, y):
    if cfg.residual_multiplier is None:
        return x + y
    return x + y * cfg.residual_multiplier


def block_apply(
    params: Dict[str, Any],
    x: jax.Array,
    positions: jax.Array,
    cfg: StackConfig,
    kind: BlockKind,
    cache: Optional[Dict[str, Any]] = None,
) -> Tuple[jax.Array, Optional[Dict[str, Any]], jax.Array]:
    """Returns (x, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if cfg.act_constraint is not None:
        x = cfg.act_constraint(x)
    # each sublayer under the scope of its kind, so that a profile of the
    # step can split device time by layer (``attn/``, ``mlp/`` ...)
    with jax.named_scope(MIXER_SCOPES[kind.mixer]):
        h = _norm(cfg, params["norm_mixer"], x)
        if kind.mixer == "attn":
            y, new_cache = attn_mod.attn_apply(params["attn"], h, positions, cfg.attn, cache)
        elif kind.mixer == "mla":
            pos1d = positions if positions.ndim == 2 else positions[..., 0]
            y, new_cache = attn_mod.mla_apply(params["mla"], h, pos1d, cfg.mla, cache)
        else:  # mamba
            y, new_cache = mamba_mod.mamba_apply(params["mamba"], h, cfg.ssm, cache)
        x = _residual(cfg, x, y)
    if kind.ffn == "mlp":
        with jax.named_scope("mlp"):
            h = _norm(cfg, params["norm_ffn"], x)
            if cfg.mlp_kind == "gelu":
                from .layers import gelu_mlp

                x = _residual(cfg, x, gelu_mlp(params["mlp"], h))
            else:
                x = _residual(cfg, x, swiglu(params["mlp"], h))
    elif kind.ffn == "moe":
        with jax.named_scope("moe"):
            h = _norm(cfg, params["norm_ffn"], x)
            y, moe_aux = moe_mod.moe_apply(params["moe"], h, cfg.moe)
            x = _residual(cfg, x, y)
            aux = aux + moe_aux
    if cfg.act_constraint is not None:
        # constrain the OUTPUT too: the scan carry is what AD stashes per
        # layer — leaving it unconstrained lets propagation pick a
        # replicated-sequence layout (measured +1.07 GiB/layer on
        # granite-8b before this constraint)
        x = cfg.act_constraint(x)
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------


def block_cache(
    kind: BlockKind, cfg: StackConfig, batch: int, max_seq: int,
    dtype: Any = jnp.bfloat16, abstract: bool = False,
):
    if kind.mixer == "attn":
        fn = attn_mod.abstract_cache if abstract else attn_mod.init_cache
        return fn(batch, max_seq, cfg.attn.n_kv_heads, cfg.attn.head_dim, dtype)
    if kind.mixer == "mla":
        fn = attn_mod.abstract_mla_cache if abstract else attn_mod.init_mla_cache
        return fn(batch, max_seq, cfg.mla, dtype)
    fn = mamba_mod.abstract_mamba_cache if abstract else mamba_mod.init_mamba_cache
    return fn(batch, cfg.ssm, dtype)


def _stack_tree(trees: List[Any]) -> Any:
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def _abstract_stack(tree: Any, n: int) -> Any:
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((n,) + tuple(s.shape), s.dtype), tree
    )


# ---------------------------------------------------------------------------
# the stack
# ---------------------------------------------------------------------------


def stack_param_defs(cfg: StackConfig) -> Dict[str, Any]:
    """Param defs for the whole decoder stack, organized by segment."""
    out: Dict[str, Any] = {}
    for si, (pattern, repeats) in enumerate(segments(cfg.layout)):
        if len(pattern) == 1:
            seg_defs = block_defs(cfg, pattern[0])
        else:
            seg_defs = {
                f"sub{bi}": block_defs(cfg, k) for bi, k in enumerate(pattern)
            }
        out[f"seg{si}"] = stack_defs(seg_defs, repeats) if repeats > 1 else seg_defs
    return out


def stack_caches(
    cfg: StackConfig, batch: int, max_seq: int,
    dtype: Any = jnp.bfloat16, abstract: bool = False,
) -> Dict[str, Any]:
    """Per-segment stacked caches (leading dim = repeats)."""
    out: Dict[str, Any] = {}
    for si, (pattern, repeats) in enumerate(segments(cfg.layout)):
        if len(pattern) == 1:
            one = block_cache(pattern[0], cfg, batch, max_seq, dtype, abstract)
        else:
            one = {
                f"sub{bi}": block_cache(k, cfg, batch, max_seq, dtype, abstract)
                for bi, k in enumerate(pattern)
            }
        if repeats > 1:
            one = (
                _abstract_stack(one, repeats)
                if abstract
                else _stack_tree([one] * repeats)
            )
        out[f"seg{si}"] = one
    return out


def stack_apply(
    params: Dict[str, Any],
    x: jax.Array,
    positions: jax.Array,
    cfg: StackConfig,
    caches: Optional[Dict[str, Any]] = None,
) -> Tuple[jax.Array, Optional[Dict[str, Any]], jax.Array]:
    """Run the full stack. Returns (x, new_caches, total_aux_loss)."""
    new_caches: Optional[Dict[str, Any]] = {} if caches is not None else None
    aux_total = jnp.zeros((), jnp.float32)

    def one_pattern(pparams, x, pattern, pcache):
        """Apply a pattern (1+ sub-blocks) once."""
        aux = jnp.zeros((), jnp.float32)
        new_pcache = {} if pcache is not None else None
        if len(pattern) == 1:
            x, nc, aux1 = block_apply(pparams, x, positions, cfg, pattern[0], pcache)
            return x, nc, aux + aux1
        for bi, kind in enumerate(pattern):
            sub = f"sub{bi}"
            c = pcache[sub] if pcache is not None else None
            x, nc, aux1 = block_apply(pparams[sub], x, positions, cfg, kind, c)
            aux = aux + aux1
            if new_pcache is not None:
                new_pcache[sub] = nc
        return x, new_pcache, aux

    # the layer loop's own work (each layer's weight and cache slices, the
    # stacked cache written back) under ``stack``; each block's under its kind
    with jax.named_scope("stack"):
        for si, (pattern, repeats) in enumerate(segments(cfg.layout)):
            seg = f"seg{si}"
            pparams = params[seg]
            pcache = caches.get(seg) if caches is not None else None
            if repeats == 1:
                x, nc, aux1 = one_pattern(pparams, x, pattern, pcache)
                aux_total = aux_total + aux1
                if new_caches is not None:
                    new_caches[seg] = nc
                continue

            def body(carry, xs):
                x, aux = carry
                p_slice, c_slice = xs
                x, nc, aux1 = one_pattern(p_slice, x, pattern, c_slice)
                return (x, aux + aux1), nc

            body_fn = jax.checkpoint(body) if cfg.remat == "full" else body
            (x, aux_total), nc_stacked = jax.lax.scan(
                body_fn, (x, aux_total), (pparams, pcache)
            )
            if new_caches is not None:
                new_caches[seg] = nc_stacked
    return x, new_caches, aux_total
