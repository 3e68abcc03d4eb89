"""Top-level model: config, layout construction, LM forward, losses.

``ModelConfig`` is the single declarative description of an architecture
(all 10 assigned archs are instances — see ``repro.configs``).  From it:

    defs    = model.param_defs()          # ParamDef tree (init/abstract/specs)
    logits  = model.apply(params, tokens) # training forward
    logits, caches = model.decode_step(params, tokens, caches)   # serving

Families:
  * decoder-only LMs (dense / MoE / SSM / hybrid / VLM-backbone) — here.
  * encoder-decoder (whisper) — ``repro.models.encdec`` (same interface).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from . import params as P
from .attention import AttnConfig, MLAConfig
from .layers import cross_entropy, embed, embed_defs, rmsnorm, rmsnorm_defs, unembed
from .mamba import SSMConfig
from .moe import MoEConfig
from .params import ParamDef
from .transformer import (
    BlockKind,
    StackConfig,
    block_defs,
    stack_apply,
    stack_caches,
    stack_param_defs,
)


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # 'dense' | 'moe' | 'ssm' | 'hybrid' | 'vlm' | 'audio'
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None
    norm: str = "rmsnorm"
    norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    use_rope: bool = True
    mrope_sections: Optional[Tuple[int, int, int]] = None
    tie_embeddings: bool = True
    sliding_window: Optional[int] = None
    attn_chunk: int = 512
    # MLA (attn_kind='mla')
    attn_kind: str = "gqa"  # 'gqa' | 'mla'
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # MoE
    mlp_kind: str = "swiglu"  # 'swiglu' | 'gelu' (gpt-bigcode style)
    n_experts: int = 0
    top_k: int = 0
    n_shared_experts: int = 0
    moe_period: int = 1  # a MoE FFN every `period` layers (jamba: 2)
    n_dense_layers: int = 0  # leading dense layers (deepseek: 3)
    dense_d_ff: Optional[int] = None  # d_ff of those dense layers
    moe_impl: str = "ragged"
    capacity_factor: float = 1.25
    # one chip's share of an expert-parallel layer: the router scores all
    # n_experts, the layer computes the n_experts_held from expert_offset
    n_experts_held: Optional[int] = None  # None: all
    expert_offset: int = 0
    shared_d_ff: Optional[int] = None  # shared expert's hidden; None: d_ff * n_shared
    # SSM / hybrid
    ssm_state: int = 0  # >0 enables mamba mixers
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_groups: int = 1
    ssm_chunk: int = 256
    hybrid_period: int = 0  # jamba: 8 (one attn layer per period)
    hybrid_attn_index: int = 4
    # granite's multipliers; None applies none (no operation is added)
    attention_multiplier: Optional[float] = None  # score scale; None: 1/sqrt(head_dim)
    embedding_multiplier: Optional[float] = None
    residual_multiplier: Optional[float] = None  # each sublayer's output
    logits_scaling: Optional[float] = None  # logits divided by it
    # MTP (deepseek)
    mtp: bool = False
    mtp_loss_weight: float = 0.3
    # enc-dec
    n_encoder_layers: int = 0
    max_source_positions: int = 1500
    # execution
    remat: str = "none"
    dtype: Any = jnp.bfloat16
    # embedding table padded up so "vocab" shards evenly over the model
    # axis (Megatron's make-vocab-size-divisible); logits include the pad
    # (trained toward -inf; labels never reference pad ids)
    vocab_pad_multiple: int = 1

    @property
    def padded_vocab(self) -> int:
        m = max(1, self.vocab_pad_multiple)
        return ((self.vocab + m - 1) // m) * m

    @property
    def head_dim_(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    # -- sub-configs -------------------------------------------------------

    def attn_config(self, causal: bool = True) -> AttnConfig:
        return AttnConfig(
            d_model=self.d_model,
            n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads,
            head_dim=self.head_dim_,
            rope_theta=self.rope_theta,
            causal=causal,
            use_rope=self.use_rope,
            mrope_sections=self.mrope_sections,
            sliding_window=self.sliding_window,
            chunk=self.attn_chunk,
            scale=self.attention_multiplier,
        )

    def mla_config(self) -> MLAConfig:
        return MLAConfig(
            d_model=self.d_model,
            n_heads=self.n_heads,
            q_lora_rank=self.q_lora_rank,
            kv_lora_rank=self.kv_lora_rank,
            qk_nope_head_dim=self.qk_nope_head_dim,
            qk_rope_head_dim=self.qk_rope_head_dim,
            v_head_dim=self.v_head_dim,
            rope_theta=self.rope_theta,
            chunk=self.attn_chunk,
        )

    def moe_config(self) -> Optional[MoEConfig]:
        if not self.n_experts:
            return None
        return MoEConfig(
            d_model=self.d_model,
            d_ff=self.d_ff,
            n_experts=self.n_experts,
            top_k=self.top_k,
            n_shared_experts=self.n_shared_experts,
            capacity_factor=self.capacity_factor,
            moe_impl=self.moe_impl,
            n_held=self.n_experts_held,
            first_expert=self.expert_offset,
            shared_d_ff=self.shared_d_ff,
        )

    def ssm_config(self) -> Optional[SSMConfig]:
        if not self.ssm_state:
            return None
        return SSMConfig(
            d_model=self.d_model,
            d_state=self.ssm_state,
            head_dim=self.ssm_head_dim,
            expand=self.ssm_expand,
            n_groups=self.ssm_groups,
            chunk=self.ssm_chunk,
            norm_eps=self.norm_eps,
        )

    # -- layout --------------------------------------------------------------

    def layout(self) -> Tuple[BlockKind, ...]:
        kinds: List[BlockKind] = []
        mixer_default = "mla" if self.attn_kind == "mla" else "attn"
        for l in range(self.n_layers):
            # mixer
            if self.ssm_state and self.hybrid_period:
                mixer = (
                    "attn" if l % self.hybrid_period == self.hybrid_attn_index else "mamba"
                )
            elif self.ssm_state:
                mixer = "mamba"
            else:
                mixer = mixer_default
            # ffn
            if self.d_ff == 0 and not self.n_experts:
                ffn = "none"
            elif self.n_experts and l >= self.n_dense_layers and (
                (l % self.moe_period) == (self.moe_period - 1) or self.moe_period == 1
            ):
                ffn = "moe"
            else:
                ffn = "mlp"
            kinds.append(BlockKind(mixer, ffn))
        return tuple(kinds)

    def stack_config(self) -> StackConfig:
        return StackConfig(
            d_model=self.d_model,
            d_ff=self.dense_d_ff or self.d_ff,
            mlp_kind=self.mlp_kind,
            layout=self.layout(),
            attn=self.attn_config(),
            mla=self.mla_config() if self.attn_kind == "mla" else None,
            ssm=self.ssm_config(),
            moe=self.moe_config(),
            norm=self.norm,
            norm_eps=self.norm_eps,
            remat=self.remat,
            residual_multiplier=self.residual_multiplier,
        )

    # -- accounting ----------------------------------------------------------

    def param_counts(self) -> Tuple[int, int]:
        """(total, active) parameter counts of the experts held: a token
        runs top_k of n_experts, so held * top_k / n_experts of those held
        on average; shared experts always run."""
        defs = LM(self).param_defs()
        total = P.param_count(defs)
        active = total
        if self.n_experts and self.top_k:
            moe_cfg = self.moe_config()
            per_expert = 3 * self.d_model * moe_cfg.d_ff
            n_moe_layers = sum(1 for k in self.layout() if k.ffn == "moe")
            idle = moe_cfg.held * (1 - self.top_k / self.n_experts)
            active = total - round(n_moe_layers * per_expert * idle)
        return total, active

    def model_flops_train(self, batch: int, seq: int) -> float:
        """6 * N_active * D (the §Roofline MODEL_FLOPS convention)."""
        _, active = self.param_counts()
        return 6.0 * active * batch * seq

    def model_flops_decode(self, batch: int) -> float:
        _, active = self.param_counts()
        return 2.0 * active * batch


# ---------------------------------------------------------------------------
# decoder-only LM
# ---------------------------------------------------------------------------


class LM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.stack_cfg = cfg.stack_config()

    # -- params ---------------------------------------------------------------

    def param_defs(self) -> Dict[str, Any]:
        cfg = self.cfg
        defs: Dict[str, Any] = {
            "embed": embed_defs(cfg.padded_vocab, cfg.d_model),
            "stack": stack_param_defs(self.stack_cfg),
            "final_norm": rmsnorm_defs(cfg.d_model),
        }
        if not cfg.tie_embeddings:
            defs["unembed"] = {
                "w_out": ParamDef(
                    (cfg.d_model, cfg.padded_vocab), ("embed", "vocab"), init="out_proj"
                )
            }
        if cfg.mtp:
            defs["mtp"] = {
                "proj": ParamDef((2 * cfg.d_model, cfg.d_model), ("embed", None)),
                "block": block_defs(
                    self.stack_cfg,
                    BlockKind("mla" if cfg.attn_kind == "mla" else "attn", "mlp"),
                ),
                "norm": rmsnorm_defs(cfg.d_model),
            }
        return defs

    def init(self, key: jax.Array, dtype: Any = None) -> Dict[str, Any]:
        return P.init_params(self.param_defs(), key, dtype or self.cfg.dtype)

    def abstract_params(self, dtype: Any = None) -> Dict[str, Any]:
        return P.abstract_params(self.param_defs(), dtype or self.cfg.dtype)

    def logical_specs(self) -> Dict[str, Any]:
        return P.logical_specs(self.param_defs())

    # -- positions -------------------------------------------------------------

    def _positions(self, tokens: jax.Array, start: Any = 0) -> jax.Array:
        b, s = tokens.shape
        pos = start + jnp.arange(s, dtype=jnp.int32)[None, :]
        pos = jnp.broadcast_to(pos, (b, s))
        if self.cfg.mrope_sections is not None:
            pos = jnp.broadcast_to(pos[..., None], (b, s, 3))  # text: t==h==w
        return pos

    # -- forward ----------------------------------------------------------------

    def apply(
        self,
        params: Dict[str, Any],
        tokens: jax.Array,  # (B, S) int32
        positions: Optional[jax.Array] = None,
        caches: Optional[Dict[str, Any]] = None,
        embeddings: Optional[jax.Array] = None,  # frontend stub path
        last_only: bool = False,  # prefill: unembed only the final position
    ) -> Tuple[jax.Array, Optional[Dict[str, Any]], jax.Array]:
        """Returns (logits (B,S,V) f32, new_caches, aux_loss)."""
        cfg = self.cfg
        if positions is None:
            start = caches_length(caches) if caches is not None else 0
            positions = self._positions(tokens, start)
        from repro.parallel.context import constrain_logical

        with jax.named_scope("embed"):
            x = embed(params["embed"], tokens).astype(cfg.dtype)
            if cfg.embedding_multiplier is not None:
                x = x * cfg.embedding_multiplier
            if embeddings is not None:
                x = x + embeddings.astype(cfg.dtype)
            # the gather from the vocab-sharded embedding leaves x with no
            # sharding for GSPMD to propagate — constrain it explicitly
            # (measured 87.7 -> 6.0 GiB/chip on whisper train_4k)
            x = constrain_logical(x, ("act_batch", "act_seq", None))
        x, new_caches, aux = stack_apply(
            params["stack"], x, positions, self.stack_cfg, caches
        )
        with jax.named_scope("unembed"):  # the final norm and the logits
            if last_only:
                x = x[:, -1:]  # slice BEFORE the (B,S,vocab) unembed matmul
            x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
            if cfg.tie_embeddings:
                logits = unembed(params["embed"], x)
            else:
                logits = (x @ params["unembed"]["w_out"].astype(x.dtype)).astype(
                    jnp.float32
                )
            if cfg.logits_scaling is not None:
                logits = logits / cfg.logits_scaling
        return logits, new_caches, aux

    # -- loss --------------------------------------------------------------------

    def loss(
        self,
        params: Dict[str, Any],
        tokens: jax.Array,  # (B, S)
        labels: jax.Array,  # (B, S) next-token targets; -1 = masked
    ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        logits, _, aux = self.apply(params, tokens)
        mask = (labels >= 0).astype(jnp.float32)
        ce = cross_entropy(logits, jnp.maximum(labels, 0), mask)
        total = ce + aux
        metrics = {"ce": ce, "aux": aux}
        if self.cfg.mtp:
            mtp_ce = self._mtp_loss(params, tokens, labels, logits)
            total = total + self.cfg.mtp_loss_weight * mtp_ce
            metrics["mtp_ce"] = mtp_ce
        metrics["loss"] = total
        return total, metrics

    def _mtp_loss(self, params, tokens, labels, logits) -> jax.Array:
        """DeepSeek-style multi-token prediction: one extra depth predicting
        t+2 from [h_t ; emb(token_{t+1})]."""
        cfg = self.cfg
        mtp = params["mtp"]
        # teacher-forced next-token embedding (shift left by 1)
        nxt = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
        e = embed(params["embed"], nxt).astype(cfg.dtype)
        # recompute trunk states cheaply from logits? No — reuse the embed of
        # argmax is wrong; the MTP block consumes the *hidden*, which we do
        # not keep.  We approximate DeepSeek's MTP at the interface level:
        # h_t ~ embed of the current token after final norm is not available,
        # so we run the MTP block on [emb(t); emb(t+1)] projected down.
        h = embed(params["embed"], tokens).astype(cfg.dtype)
        x = jnp.concatenate([h, e], axis=-1) @ mtp["proj"].astype(cfg.dtype)
        pos = self._positions(tokens)
        kind = BlockKind("mla" if cfg.attn_kind == "mla" else "attn", "mlp")
        from .transformer import block_apply  # local to avoid cycle

        x, _, _ = block_apply(mtp["block"], x, pos, self.stack_cfg, kind)
        x = rmsnorm(mtp["norm"], x, cfg.norm_eps)
        mtp_logits = unembed(params["embed"], x)
        # targets shifted one further: predict labels[t+1] at position t
        tgt = jnp.pad(labels[:, 1:], ((0, 0), (0, 1)), constant_values=-1)
        mask = (tgt >= 0).astype(jnp.float32)
        return cross_entropy(mtp_logits, jnp.maximum(tgt, 0), mask)

    # -- serving -----------------------------------------------------------------

    def init_caches(
        self, batch: int, max_seq: int, dtype: Any = jnp.bfloat16, abstract: bool = False
    ) -> Dict[str, Any]:
        return stack_caches(self.stack_cfg, batch, max_seq, dtype, abstract)

    def decode_step(
        self,
        params: Dict[str, Any],
        tokens: jax.Array,  # (B, 1)
        caches: Dict[str, Any],
    ) -> Tuple[jax.Array, Dict[str, Any]]:
        logits, new_caches, _ = self.apply(params, tokens, caches=caches)
        return logits, new_caches

    def prefill(
        self,
        params: Dict[str, Any],
        tokens: jax.Array,  # (B, S)
        caches: Dict[str, Any],
        last_only: bool = False,  # logits (B, 1, V) of the final position only
    ) -> Tuple[jax.Array, Dict[str, Any]]:
        logits, new_caches, _ = self.apply(params, tokens, caches=caches, last_only=last_only)
        return logits, new_caches


def caches_length(caches: Optional[Dict[str, Any]]) -> Any:
    """Current sequence length of a cache tree (0 for pure-SSM caches)."""
    if caches is None:
        return 0
    lengths = [
        leaf
        for path, leaf in jax.tree_util.tree_flatten_with_path(caches)[0]
        if any(getattr(k, "key", None) == "length" for k in path)
    ]
    if not lengths:
        return 0
    # stacked (per-layer) lengths are all equal; take the first element
    leaf = lengths[0]
    if hasattr(leaf, "reshape"):
        return jnp.reshape(leaf, (-1,))[0]
    return leaf


def build_model(cfg: ModelConfig):
    """Family dispatch: decoder-only here, enc-dec in encdec.py."""
    if cfg.family == "audio" or cfg.n_encoder_layers:
        from .encdec import EncDec

        return EncDec(cfg)
    return LM(cfg)
