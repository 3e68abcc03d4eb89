"""Mixture-of-Experts: top-k routing, two dispatch strategies, EP sharding.

Dispatch strategies (config ``moe_impl``):

  * ``'ragged'``  (default) — dropless sort-based dispatch: flatten
    (token, expert) assignments, sort by expert, run
    ``jax.lax.ragged_dot`` grouped matmuls, unsort, weighted-combine.
    Zero dropped tokens, active-FLOPs-only compute; the sort+gather is
    the only overhead.  This is the MaxText/megablox formulation; the
    Pallas ``gmm`` kernel in ``repro.kernels.gmm`` is its TPU hot path.

  * ``'capacity'`` — GShard-style fixed-capacity scatter dispatch into an
    (E, C, d) buffer, einsum expert compute, gather combine.  Tokens
    beyond capacity are dropped (counted).  Compiles to a static shape
    friendly to expert-parallel sharding; used as the paper-baseline
    comparison point in §Perf.

Experts shard over the logical ``expert`` axis (-> mesh model axis) for
EP; the router is replicated.

A layer may hold a share of the experts (``n_held`` from ``first_expert``),
as one chip of an expert-parallel deployment does: the router still
scores all ``n_experts`` and keeps the full top-k weights, and the layer
returns the part of the result that its own experts give (the shared
expert included).  Only the ragged path takes that cut; the others raise.

On the ragged path a profile splits the layer's work into ``route``
(router, top-k, the sort and gather of the dispatch), ``experts`` (the
grouped matmuls and the combine) and ``shared`` (the always-on expert).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .params import ParamDef


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int  # per-expert hidden
    n_experts: int
    top_k: int
    n_shared_experts: int = 0  # DeepSeek-style always-on experts
    capacity_factor: float = 1.25
    moe_impl: str = "ragged"  # 'ragged' | 'capacity'
    router_noise: float = 0.0
    aux_loss_weight: float = 0.01
    n_held: Optional[int] = None  # experts held here; None: all
    first_expert: int = 0  # id of the first held expert
    shared_d_ff: Optional[int] = None  # shared hidden; None: d_ff * n_shared_experts

    @property
    def held(self) -> int:
        return self.n_experts if self.n_held is None else self.n_held

    @property
    def shared_width(self) -> int:
        return self.shared_d_ff or self.d_ff * self.n_shared_experts


def moe_defs(cfg: MoEConfig) -> Dict[str, ParamDef]:
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    defs = {
        "router": ParamDef((d, e), ("embed", None), scale=0.1),
        "w_gate": ParamDef((cfg.held, d, f), ("expert", "embed", "mlp")),
        "w_up": ParamDef((cfg.held, d, f), ("expert", "embed", "mlp")),
        "w_down": ParamDef((cfg.held, f, d), ("expert", "mlp", "embed"), init="out_proj"),
    }
    if cfg.n_shared_experts:
        fs = cfg.shared_width
        defs.update(
            {
                "shared_w_gate": ParamDef((d, fs), ("embed", "mlp")),
                "shared_w_up": ParamDef((d, fs), ("embed", "mlp")),
                "shared_w_down": ParamDef((fs, d), ("mlp", "embed"), init="out_proj"),
            }
        )
    return defs


def _router(params, x2d, cfg: MoEConfig, rng=None):
    """Router logits -> (top-k expert ids, normalized weights, aux loss)."""
    logits = (x2d @ params["router"].astype(x2d.dtype)).astype(jnp.float32)
    if cfg.router_noise > 0.0 and rng is not None:
        logits = logits + cfg.router_noise * jax.random.normal(rng, logits.shape)
    probs = jax.nn.softmax(logits, axis=-1)  # (T, E)
    top_w, top_e = jax.lax.top_k(probs, cfg.top_k)  # (T, k)
    top_w = top_w / jnp.maximum(jnp.sum(top_w, axis=-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss: E * sum_e f_e * p_e
    t = x2d.shape[0]
    me = jnp.mean(probs, axis=0)  # mean router prob per expert
    ce = jnp.zeros((cfg.n_experts,), jnp.float32).at[top_e[:, 0]].add(1.0) / t
    aux = cfg.n_experts * jnp.sum(me * ce) * cfg.aux_loss_weight
    return top_e, top_w.astype(x2d.dtype), aux


def _expert_ffn_ragged(params, xs, group_sizes, dtype):
    """Grouped SwiGLU over expert-sorted rows via ragged_dot."""
    g = jax.lax.ragged_dot(xs, params["w_gate"].astype(dtype), group_sizes)
    u = jax.lax.ragged_dot(xs, params["w_up"].astype(dtype), group_sizes)
    h = (jax.nn.silu(g.astype(jnp.float32)).astype(dtype)) * u
    return jax.lax.ragged_dot(h, params["w_down"].astype(dtype), group_sizes)


def moe_apply_ragged(
    params: Dict[str, jax.Array],
    x: jax.Array,  # (B, S, d)
    cfg: MoEConfig,
    rng: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Dropless sort-based MoE. Returns (y, aux_loss)."""
    b, s, d = x.shape
    x2d = x.reshape(b * s, d)
    t = b * s
    cut = cfg.held < cfg.n_experts
    with jax.named_scope("route"):
        top_e, top_w, aux = _router(params, x2d, cfg, rng)

        # flatten (token, slot) pairs and sort by expert id
        flat_e = top_e.reshape(-1)  # (T*k,)
        if cut:  # held experts by local id; the others sort past them
            flat_e = flat_e - cfg.first_expert
            mine = (flat_e >= 0) & (flat_e < cfg.held)
            flat_e = jnp.where(mine, flat_e, cfg.held)
        token_idx = jnp.repeat(jnp.arange(t), cfg.top_k)
        order = jnp.argsort(flat_e)  # stable
        sorted_tokens = token_idx[order]
        xs = x2d[sorted_tokens]  # (T*k, d) gather
        # rows past the held groups' sum belong to no group
        group_sizes = jnp.bincount(flat_e, length=cfg.held).astype(jnp.int32)

    with jax.named_scope("experts"):
        ys = _expert_ffn_ragged(params, xs, group_sizes, x.dtype)  # (T*k, d)

        # unsort + weighted combine
        inv = jnp.zeros_like(order).at[order].set(jnp.arange(order.shape[0]))
        ys = ys[inv].reshape(t, cfg.top_k, d)
        if cut:  # an absent expert's assignment adds nothing
            ys = jnp.where(mine.reshape(t, cfg.top_k, 1), ys, 0)
        y = jnp.einsum("tkd,tk->td", ys, top_w.astype(ys.dtype))
        y = y.astype(x.dtype)
    if cfg.n_shared_experts:
        with jax.named_scope("shared"):
            y = y + _shared_ffn(params, x2d)
    return y.reshape(b, s, d), aux


def _require_all_held(cfg: MoEConfig, path: str) -> None:
    if cfg.held < cfg.n_experts:
        raise NotImplementedError(
            f"the {path} dispatch holds every expert; a share of "
            f"{cfg.held} of {cfg.n_experts} runs on the ragged path only")


def moe_apply_capacity(
    params: Dict[str, jax.Array],
    x: jax.Array,
    cfg: MoEConfig,
    rng: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """GShard-style GROUPED capacity dispatch (drops overflow).

    Tokens are grouped by the leading batch dim (groups stay data-sharded
    end-to-end); capacity is per (group, expert), so the position cumsum
    is (G, S, E) — local to a group, never a global (T, E) tensor (the
    ungrouped formulation measured 645 GiB/chip on deepseek train_4k).
    The expert einsum moves (G, E, C, d) between the data-sharded G
    layout and the model-sharded E layout: the classic 2x all-to-all of
    expert parallelism, inserted by GSPMD.
    """
    _require_all_held(cfg, "capacity")
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    x2d = x.reshape(b * s, d)
    top_e, top_w, aux = _router(params, x2d, cfg, rng)
    cap = max(k, int(cfg.capacity_factor * s * k / e))

    # (G, S*k) expert assignment per group
    ge = top_e.reshape(b, s * k)
    onehot = jax.nn.one_hot(ge, e, dtype=jnp.int32)  # (G, S*k, E)
    pos = jnp.einsum(
        "gse,gse->gs", jnp.cumsum(onehot, axis=1) - onehot, onehot
    )  # (G, S*k) position within (group, expert) queue
    keep = pos < cap
    e_idx = jnp.where(keep, ge, e)  # dropped -> OOB expert row
    p_idx = jnp.where(keep, pos, 0)
    token_in_group = jnp.repeat(jnp.arange(s), k)[None].repeat(b, 0)  # (G, S*k)

    # scatter into the (G, E+1, C, d) dispatch buffer (group-local scatter)
    from repro.parallel.context import constrain_logical

    xg = x  # (G, S, d)
    disp = jnp.zeros((b, e + 1, cap, d), x.dtype)
    gi = jnp.arange(b)[:, None].repeat(s * k, 1)
    disp = disp.at[gi, e_idx, p_idx].set(
        jnp.take_along_axis(xg, token_in_group[..., None], axis=1), mode="drop"
    )
    disp = disp[:, :e]
    # EP layout: groups stay data-sharded, experts shard over the model
    # axis (GSPMD inserts the classic pair of all-to-alls around the
    # expert compute); without this constraint the (G,E,C,d) buffers were
    # left expert-replicated: +9 GiB/layer on deepseek train_4k
    disp = constrain_logical(disp, ("act_batch", "expert", None, None))

    g = jnp.einsum("gecd,edf->gecf", disp, params["w_gate"].astype(x.dtype))
    u = jnp.einsum("gecd,edf->gecf", disp, params["w_up"].astype(x.dtype))
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    eo = jnp.einsum("gecf,efd->gecd", h, params["w_down"].astype(x.dtype))
    eo = constrain_logical(eo, ("act_batch", "expert", None, None))

    # gather back per (group, token, slot), weight, sum over slots
    yk = eo[gi, e_idx.clip(0, e - 1), p_idx]  # (G, S*k, d)
    yk = jnp.where(keep[..., None], yk, 0.0).reshape(b, s, k, d)
    w = top_w.reshape(b, s, k)
    y = jnp.einsum("gskd,gsk->gsd", yk, w.astype(yk.dtype)).astype(x.dtype)
    if cfg.n_shared_experts:
        y = y.reshape(b * s, d) + _shared_ffn(params, x2d)
        y = y.reshape(b, s, d)
    return y, aux


def _shared_ffn(params, x2d):
    g = x2d @ params["shared_w_gate"].astype(x2d.dtype)
    u = x2d @ params["shared_w_up"].astype(x2d.dtype)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x2d.dtype) * u
    return h @ params["shared_w_down"].astype(x2d.dtype)


# ---------------------------------------------------------------------------
# EP via shard_map: explicit all-to-all expert parallelism
# ---------------------------------------------------------------------------


def moe_apply_ep(
    params: Dict[str, jax.Array],
    x: jax.Array,  # (B, S, d) — seq must divide the model axis
    cfg: MoEConfig,
    rng: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Expert parallelism with explicit all-to-alls (the DeepSeek/GShard
    production pattern), implemented with shard_map.

    Layout: tokens enter (batch over data, seq over model); each device
    routes its local tokens, locally scatters them into an (E, C, d) send
    buffer, ALL-TO-ALLs over the model axis so each device receives the
    slots of its own E/model experts, runs the local expert FFN, and
    all-to-alls back.  Exactly two all-to-alls per MoE layer — versus the
    GSPMD-routed capacity path whose scatter lowered to ~10x the wire
    bytes on deepseek-v3 train_4k (see EXPERIMENTS.md §Perf).
    """
    from jax.sharding import PartitionSpec as P

    from repro.parallel.context import active_mesh, active_rules

    _require_all_held(cfg, "expert-parallel")
    mesh = active_mesh()
    rules = active_rules()
    if (
        mesh is None
        or rules is None
        or "model" not in getattr(mesh, "axis_names", ())
    ):
        return moe_apply_capacity(params, x, cfg, rng)
    msize = mesh.shape["model"]
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    if s % msize:
        return moe_apply_capacity(params, x, cfg, rng)
    # expert placement axes from the rules ("model", or ("model","data")
    # when every chip owns whole experts); fall back to model-only when
    # the expert count doesn't divide
    ep_axes = tuple(rules.get("expert")) or ("model",)
    ep_size = 1
    for a in ep_axes:
        ep_size *= mesh.shape[a]
    if e % ep_size:
        ep_axes = ("model",)
        ep_size = msize
    if e % ep_size:
        return moe_apply_capacity(params, x, cfg, rng)
    e_local = e // ep_size
    batch_axes = tuple(rules.get("act_batch"))
    bsize = 1
    for a in batch_axes:
        bsize *= mesh.shape[a]
    bpart = batch_axes if b % max(bsize, 1) == 0 and bsize > 1 else None

    def local_fn(router_w, w_gate, w_up, w_down, x_loc):
        # x_loc: (B_loc, S_loc, d); weights: (e_local, d, f) etc.
        bl, sl, _ = x_loc.shape
        t = bl * sl
        x2 = x_loc.reshape(t, d)
        logits = (x2 @ router_w).astype(jnp.float32)  # (t, E) router replicated
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_e = jax.lax.top_k(probs, k)
        top_w = (top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)).astype(
            x_loc.dtype
        )
        cap = max(k, int(cfg.capacity_factor * t * k / e))

        # local scatter into the (E, C, d) send buffer
        flat_e = top_e.reshape(-1)  # (t*k,)
        onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
        pos = jnp.einsum("te,te->t", jnp.cumsum(onehot, 0) - onehot, onehot)
        keep = pos < cap
        e_idx = jnp.where(keep, flat_e, e)
        p_idx = jnp.where(keep, pos, 0)
        tok = jnp.repeat(jnp.arange(t), k)
        send = jnp.zeros((e + 1, cap, d), x_loc.dtype)
        send = send.at[e_idx, p_idx].set(x2[tok], mode="drop")[:e]

        # exchange: each device keeps slots for its own e_local experts
        recv = jax.lax.all_to_all(
            send.reshape(ep_size, e_local, cap, d), ep_axes,
            split_axis=0, concat_axis=0, tiled=False,
        )  # (ep_size, e_local, cap, d): dim0 = source shard
        xs = recv.transpose(1, 0, 2, 3).reshape(e_local, ep_size * cap, d)

        g = jnp.einsum("ecd,edf->ecf", xs, w_gate)
        u = jnp.einsum("ecd,edf->ecf", xs, w_up)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x_loc.dtype) * u
        eo = jnp.einsum("ecf,efd->ecd", h, w_down)  # (e_local, ep_size*cap, d)

        # return path
        back = eo.reshape(e_local, ep_size, cap, d).transpose(1, 0, 2, 3)
        mine = jax.lax.all_to_all(
            back, ep_axes, split_axis=0, concat_axis=0, tiled=False
        ).reshape(e, cap, d)  # my tokens' processed slots

        yk = mine[e_idx.clip(0, e - 1), p_idx]
        yk = jnp.where(keep[:, None], yk, 0.0).reshape(t, k, d)
        y = jnp.einsum("tkd,tk->td", yk, top_w.astype(yk.dtype))

        # load-balance aux (Switch) averaged over all devices
        me = jnp.mean(probs, axis=0)
        ce = jnp.zeros((e,), jnp.float32).at[top_e[:, 0]].add(1.0) / t
        aux = e * jnp.sum(me * ce) * cfg.aux_loss_weight
        aux = jax.lax.pmean(aux, "model")
        for a in batch_axes:
            aux = jax.lax.pmean(aux, a)
        return y.reshape(bl, sl, d).astype(x_loc.dtype), aux

    xspec = P(bpart, "model", None)
    wspec = P(ep_axes if len(ep_axes) > 1 else ep_axes[0], None, None)
    y, aux = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(None, None), wspec, wspec, wspec, xspec),
        out_specs=(xspec, P()),
        check_vma=False,
    )(
        params["router"].astype(x.dtype),
        params["w_gate"].astype(x.dtype),
        params["w_up"].astype(x.dtype),
        params["w_down"].astype(x.dtype),
        x,
    )
    if cfg.n_shared_experts:
        y = y + _shared_ffn(params, x.reshape(b * s, d)).reshape(b, s, d)
    return y, aux


def moe_apply(
    params: Dict[str, jax.Array],
    x: jax.Array,
    cfg: MoEConfig,
    rng: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    if cfg.moe_impl == "ep":
        return moe_apply_ep(params, x, cfg, rng)
    if cfg.moe_impl == "capacity":
        return moe_apply_capacity(params, x, cfg, rng)
    return moe_apply_ragged(params, x, cfg, rng)


def moe_ref(
    params: Dict[str, jax.Array], x: jax.Array, cfg: MoEConfig
) -> Tuple[jax.Array, jax.Array]:
    """Dense oracle: run every token through every held expert, weight by
    the full top-k gate. O(E) compute — tests only."""
    b, s, d = x.shape
    x2d = x.reshape(b * s, d)
    top_e, top_w, aux = _router(params, x2d, cfg)
    g = jnp.einsum("td,edf->tef", x2d, params["w_gate"].astype(x.dtype))
    u = jnp.einsum("td,edf->tef", x2d, params["w_up"].astype(x.dtype))
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    eo = jnp.einsum("tef,efd->ted", h, params["w_down"].astype(x.dtype))
    w_full = jnp.zeros((b * s, cfg.n_experts), x.dtype)
    for k in range(cfg.top_k):
        w_full = w_full.at[jnp.arange(b * s), top_e[:, k]].add(top_w[:, k])
    w_held = w_full[:, cfg.first_expert:cfg.first_expert + cfg.held]
    y = jnp.einsum("ted,te->td", eo, w_held)
    if cfg.n_shared_experts:
        y = y + _shared_ffn(params, x2d)
    return y.reshape(b, s, d), aux
