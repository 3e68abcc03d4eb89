"""The profiler's model bridge: CI-sized models and their derived kernels.

``MODELS`` holds tiny-but-real :class:`~repro.models.model.ModelConfig`
instances — one per family the repo ships (dense transformer, MoE,
Mamba) — each paired with the profile shapes (batch, seq) that
``cuthermo model`` runs at.  Sizes are chosen so a full per-layer
profile plus a forward/backward numerical pass stay comfortably inside
a CI worker.

``kernel_entry`` synthesizes a :class:`repro.kernels.RegistryEntry` for
references of the form ``model.<model>.<kind>`` (kind ∈ attn / mlp /
moe / ssm / unembed), with the spec shapes derived from the model
config.  ``repro.kernels.get`` delegates those names here, which makes
every model-derived kernel a first-class family for ``cuthermo
profile/lint/tune/check`` — including sharded workers, which rebuild
specs from their ``model.…:variant`` source stamps via
``kernels.build``.

This module belongs to the profiler: it imports the model and the
kernels, and neither imports it back (``repro.kernels.get`` reaches it
by a call-time import).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence, Tuple

import jax.numpy as jnp

from repro.core.spec import KernelSpec
from repro.kernels import KernelVariant, RegistryEntry, flash, gemm, gmm, ssd
from repro.models.model import ModelConfig

__all__ = [
    "KIND_RUNGS",
    "MODELS",
    "ModelEntry",
    "apply_overrides",
    "get_model",
    "kernel_entry",
    "kernel_kinds",
    "kind_spec",
    "layer_kinds",
    "model_names",
]


@dataclasses.dataclass(frozen=True)
class ModelEntry:
    """A registered model: the config plus its default profile shapes."""

    config: ModelConfig
    batch: int
    seq: int
    summary: str = ""


MODELS: Dict[str, ModelEntry] = {
    "transformer-tiny": ModelEntry(
        config=ModelConfig(
            name="transformer-tiny",
            family="dense",
            n_layers=2,
            d_model=128,
            n_heads=4,
            n_kv_heads=4,
            d_ff=256,
            vocab=512,
            head_dim=32,
            attn_chunk=64,
            dtype=jnp.float32,
        ),
        batch=2,
        seq=64,
        summary="2-layer dense transformer (attn + swiglu MLP)",
    ),
    "moe-tiny": ModelEntry(
        config=ModelConfig(
            name="moe-tiny",
            family="moe",
            n_layers=2,
            d_model=128,
            n_heads=4,
            n_kv_heads=4,
            d_ff=128,
            vocab=512,
            head_dim=32,
            attn_chunk=64,
            n_experts=4,
            top_k=2,
            moe_period=1,
            dtype=jnp.float32,
        ),
        batch=2,
        seq=64,
        summary="2-layer MoE transformer (attn + 4-expert ragged MoE)",
    ),
    "mamba-tiny": ModelEntry(
        config=ModelConfig(
            name="mamba-tiny",
            family="ssm",
            n_layers=2,
            d_model=128,
            n_heads=4,
            n_kv_heads=4,
            d_ff=0,
            vocab=512,
            attn_chunk=64,
            ssm_state=16,
            ssm_head_dim=32,
            ssm_expand=2,
            ssm_chunk=32,
            dtype=jnp.float32,
        ),
        batch=2,
        seq=64,
        summary="2-layer Mamba-2 SSD stack (no FFN)",
    ),
}


def model_names() -> Tuple[str, ...]:
    """All registered model names, stable order."""
    return tuple(MODELS)


def get_model(name: str) -> ModelEntry:
    """Look up a model entry; raises KeyError with the known names."""
    try:
        return MODELS[name]
    except KeyError:
        raise KeyError(
            f"unknown model {name!r}; known: {', '.join(MODELS)}"
        ) from None


def apply_overrides(cfg: ModelConfig, overrides: Sequence[str]) -> ModelConfig:
    """Apply CLI ``key=value`` overrides, coercing to the field's type.

    Coercion follows the *current* value's type (int/float/bool/str);
    unknown keys and malformed pairs raise ``ValueError`` so the CLI can
    map them to exit code 2.
    """
    fields = {f.name: f for f in dataclasses.fields(cfg)}
    changes: Dict[str, object] = {}
    for item in overrides:
        key, sep, raw = item.partition("=")
        if not sep or not key:
            raise ValueError(f"override {item!r} is not of the form key=value")
        if key not in fields:
            raise ValueError(
                f"unknown config field {key!r}; known: "
                f"{', '.join(sorted(fields))}"
            )
        current = getattr(cfg, key)
        if isinstance(current, bool):
            if raw.lower() not in ("true", "false", "0", "1"):
                raise ValueError(f"override {key}: expected bool, got {raw!r}")
            changes[key] = raw.lower() in ("true", "1")
        elif isinstance(current, int):
            try:
                changes[key] = int(raw)
            except ValueError:
                raise ValueError(
                    f"override {key}: expected int, got {raw!r}"
                ) from None
        elif isinstance(current, float):
            try:
                changes[key] = float(raw)
            except ValueError:
                raise ValueError(
                    f"override {key}: expected float, got {raw!r}"
                ) from None
        else:
            changes[key] = raw
    return dataclasses.replace(cfg, **changes)


# ---------------------------------------------------------------------------
# model → kernel derivation
# ---------------------------------------------------------------------------

# layout() block kinds -> the kernel kind that implements them
_MIXER_KIND = {"attn": "attn", "mla": "attn", "mamba": "ssm"}
_FFN_KIND = {"mlp": "mlp", "moe": "moe", "none": None}


def layer_kinds(cfg: ModelConfig) -> List[Tuple[str, str]]:
    """(layer path, kernel kind) pairs of one forward pass, in order.

    Each block contributes its mixer's kind, then its FFN's (if any);
    the pass always ends with ``("head", "unembed")``, the logits GEMM.
    """
    pairs: List[Tuple[str, str]] = []
    for i, block in enumerate(cfg.layout()):
        path = f"layer{i}"
        pairs.append((path, _MIXER_KIND[block.mixer]))
        ffn = _FFN_KIND[block.ffn]
        if ffn is not None:
            pairs.append((path, ffn))
    pairs.append(("head", "unembed"))
    return pairs


def kernel_kinds(cfg: ModelConfig) -> Tuple[str, ...]:
    """Distinct kernel kinds of :func:`layer_kinds`, first-seen order.

    Always ends with ``unembed``: every LM closes with the logits GEMM.
    """
    return tuple(dict.fromkeys(kind for _, kind in layer_kinds(cfg)))


def kind_spec(
    cfg: ModelConfig, kind: str, batch: int, seq: int, rung: int = 0
) -> KernelSpec:
    """Build the KernelSpec for one kernel kind at the model's shapes.

    ``rung=0`` is the baseline derivation; ``rung=1`` the optimized one
    (wider KV blocks for attention, the blocked VMEM-accumulator GEMM
    for the MLP, wider expert tiles for MoE).  The SSD scan has a single
    rung.  Raises ``ValueError`` for a kind the config doesn't use.
    """
    if kind not in kernel_kinds(cfg):
        raise ValueError(
            f"model {cfg.name!r} has no {kind!r} kernels "
            f"(layout uses: {', '.join(kernel_kinds(cfg))})"
        )
    tokens = batch * seq
    if kind == "attn":
        d = cfg.head_dim_
        bq = min(32, seq)
        bkv = min(32, seq) if rung == 0 else min(64, seq)
        return flash.flash_spec(
            batch * cfg.n_heads, seq, seq, d, bq=bq, bkv=bkv
        )
    if kind == "mlp":
        m, n, k = tokens, cfg.d_ff, cfg.d_model
        if rung == 0:
            return gemm.gemm_v01_spec(m, n, k, bm=8)
        bm = min(64, m)
        return gemm.gemm_v02_spec(m, n, k, bm=bm, bn=bm, bk=bm)
    if kind == "moe":
        m, k, n = tokens, cfg.d_model, cfg.d_ff
        bm = 32 if rung == 0 else 64
        bm = min(bm, m)
        ids = gmm.expert_ids(m // bm, cfg.n_experts)
        return gmm.gmm_spec(m, k, n, cfg.n_experts, ids, bm=bm)
    if kind == "ssm":
        d_inner = cfg.d_model * cfg.ssm_expand
        n_heads = max(1, d_inner // cfg.ssm_head_dim)
        chunk = min(cfg.ssm_chunk, seq)
        return ssd.ssd_chunk_spec(
            batch * n_heads, seq // chunk, chunk, cfg.ssm_head_dim,
            cfg.ssm_state,
        )
    if kind == "unembed":
        m, n, k = tokens, cfg.padded_vocab, cfg.d_model
        if rung == 0:
            return gemm.gemm_v01_spec(m, n, k, bm=8)
        bm = min(64, m)
        return gemm.gemm_v02_spec(m, n, k, bm=bm, bn=bm, bk=bm)
    raise ValueError(f"unknown kernel kind {kind!r}")


_KIND_SUMMARY = {
    "attn": "flash attention at the model's (heads, seq, head_dim)",
    "mlp": "FFN GEMM at (tokens, d_ff, d_model): v01 tile vs v02 blocked",
    "moe": "MoE expert dispatch GMM with seeded sorted expert ids",
    "ssm": "Mamba SSD chunk scan at the model's state shapes",
    "unembed": "logits GEMM at (tokens, padded_vocab, d_model)",
}

KIND_RUNGS = {
    "attn": (("base", "dense bq=bkv tiling"),
             ("wide-kv", "wider KV blocks: fewer Q reloads")),
    "mlp": (("v01", "tile-per-program GEMM"),
            ("v02", "blocked GEMM + VMEM accumulator")),
    "moe": (("tile32", "32-row expert tiles"),
            ("tile64", "64-row tiles: half the W fetches")),
    "ssm": (("chunk", "per-(head,chunk) state streaming"),),
    "unembed": (("v01", "tile-per-program GEMM"),
                ("v02", "blocked GEMM + VMEM accumulator")),
}


def kernel_entry(ref: str):
    """Synthesize the RegistryEntry for a ``model.<model>.<kind>`` family.

    Raises ``KeyError`` (matching ``repro.kernels.get``'s contract) for
    malformed refs, unknown models, and kinds the model doesn't use.
    """
    parts = ref.split(".")
    if len(parts) != 3 or parts[0] != "model":
        raise KeyError(
            f"model-derived kernel refs look like model.<model>.<kind>, "
            f"got {ref!r}"
        )
    _, model_name, kind = parts
    entry = get_model(model_name)  # KeyError on unknown model
    cfg = entry.config
    if kind not in kernel_kinds(cfg):
        raise KeyError(
            f"model {model_name!r} has no {kind!r} kernels "
            f"(layout uses: {', '.join(kernel_kinds(cfg))})"
        )
    variants = tuple(
        KernelVariant(
            name=rung_name,
            build=(
                lambda c=cfg, k=kind, b=entry.batch, s=entry.seq, r=rung:
                kind_spec(c, k, b, s, rung=r)
            ),
            role="baseline" if rung == 0 else "optimized",
            note=note,
        )
        for rung, (rung_name, note) in enumerate(KIND_RUNGS[kind])
    )
    return RegistryEntry(
        name=ref,
        summary=f"{model_name}: {_KIND_SUMMARY[kind]}",
        variants=variants,
    )
