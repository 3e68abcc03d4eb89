"""repro.kernels — Pallas TPU kernels for the perf-critical hot spots.

Each module ships: the ``pl.pallas_call`` kernel (TPU target, validated
with interpret=True on CPU), a profiler ``KernelSpec`` builder (the
CUTHERMO instrumentation path), plus ``ops`` (jit wrappers) and ``ref``
(pure-jnp oracles).

This package also hosts the **kernel registry** used by the ``cuthermo``
CLI and the session subsystem: every case-study kernel is addressable by
name (``gemm``, ``spmv``, ...) with an ordered set of *variants* walking
the paper's optimization ladder (``gemm:v00`` the false-sharing naive
kernel, ``gemm:v01`` the re-tiled fix, ...).  A variant bundles a
ready-to-profile ``KernelSpec`` at representative default shapes with
the deterministic dynamic context (seeded index arrays) the Level-2
walkers need — so ``cuthermo profile --kernel spmv`` works with zero
setup.

The ladder is also the autotuner's candidate source: ``cuthermo tune``
walks each family's ``role='optimized'`` variants forward
(:meth:`RegistryEntry.ladder`) alongside the generated candidates it
synthesizes from advisor actions (see ``repro.core.tuner``).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import numpy as np

from repro.core.spec import KernelSpec
from repro.core.trace import GridSampler

from . import (
    flash, gemm, gmm, gramschm, histogram, ops, paged_attn, ragged_flash,
    ref, spmv, ssd, ttm,
)


@dataclasses.dataclass(frozen=True)
class KernelVariant:
    """One profile-ready point on a kernel's optimization ladder."""

    name: str
    build: Callable[[], KernelSpec]
    context: Optional[Callable[[], Dict[str, np.ndarray]]] = None
    role: str = "baseline"  # 'baseline' | 'optimized'
    note: str = ""

    def spec(self) -> KernelSpec:
        """Build the KernelSpec at the registry's default shapes."""
        return self.build()

    def dynamic_context(self) -> Optional[Dict[str, np.ndarray]]:
        """Deterministic dynamic context (seeded), or None if not needed."""
        return self.context() if self.context is not None else None


def _full() -> GridSampler:
    # Full-grid sampling is the registry default: the columnar engine makes
    # it cheap at these shapes, and aligned (whole-problem) coverage is what
    # lets two variants' transfer totals diff meaningfully.  The paper's
    # thread-block sampling remains available via --sampler window:N.
    return GridSampler(None)


@dataclasses.dataclass(frozen=True)
class RegistryEntry:
    """One named kernel family: variants + the sampler that suits it."""

    name: str
    summary: str
    variants: Tuple[KernelVariant, ...]
    sampler: Callable[[], GridSampler] = _full
    region_map: Tuple[Tuple[str, str], ...] = ()  # baseline->optimized renames

    def variant(self, name: Optional[str] = None) -> KernelVariant:
        """Look up a variant by name; the first (baseline) is the default."""
        if name is None:
            return self.variants[0]
        for v in self.variants:
            if v.name == name:
                return v
        raise KeyError(
            f"kernel {self.name!r} has no variant {name!r} "
            f"(have {[v.name for v in self.variants]})"
        )

    def variant_names(self) -> Tuple[str, ...]:
        """All variant names, baseline first."""
        return tuple(v.name for v in self.variants)

    def ladder(self, min_position: int = 0) -> Tuple[Tuple[int, "KernelVariant"], ...]:
        """The family's optimization ladder: (position, variant) pairs.

        Only ``role='optimized'`` variants, in published (paper) order,
        starting at ``min_position`` — the autotuner walks this forward
        (``repro.core.tuner.ladder_candidates``) and never revisits a
        rung at or below the one it accepted.
        """
        return tuple(
            (pos, v)
            for pos, v in enumerate(self.variants)
            if v.role == "optimized" and pos >= min_position
        )


def _spmv_context() -> Dict[str, np.ndarray]:
    rng = np.random.default_rng(0)
    return {
        "col_indices": rng.integers(0, 36417, size=65536).astype(np.int32)
    }


def _hist_context() -> Dict[str, np.ndarray]:
    rng = np.random.default_rng(0)
    return {"cells": rng.integers(0, 2048, size=65536).astype(np.int64)}


REGISTRY: Dict[str, RegistryEntry] = {
    e.name: e
    for e in (
        RegistryEntry(
            name="gemm",
            summary="dense matmul ladder: false sharing -> re-tiled -> "
            "blocked with VMEM accumulator (paper §VI-A)",
            variants=(
                KernelVariant(
                    "v00",
                    lambda: gemm.gemm_v00_spec(1024, 1024, 1024),
                    note="row-per-program: false sharing on C, hot B",
                ),
                KernelVariant(
                    "v01",
                    lambda: gemm.gemm_v01_spec(1024, 1024, 1024),
                    role="optimized",
                    note="tile-per-program: the re-tile fix",
                ),
                KernelVariant(
                    "v02",
                    lambda: gemm.gemm_v02_spec(1024, 1024, 1024),
                    role="optimized",
                    note="blocked (bm,bn,bk) + VMEM accumulator",
                ),
            ),
            sampler=_full,
        ),
        RegistryEntry(
            name="spmv",
            summary="CSR SpMV: misaligned rowOffsets view + x gather vs "
            "the zigzag duplicated-pairs fix (paper Fig. 7)",
            variants=(
                KernelVariant(
                    "csr",
                    lambda: spmv.spmv_csr_spec(65536, 36417),
                    context=_spmv_context,
                    note="shifted rowOffsets load straddles every tile",
                ),
                KernelVariant(
                    "zigzag",
                    lambda: spmv.spmv_zigzag_spec(65536, 36417),
                    context=_spmv_context,
                    role="optimized",
                    note="duplicated (start,end) pairs, one aligned load",
                ),
            ),
            sampler=_full,
        ),
        RegistryEntry(
            name="histogram",
            summary="GPUMD-style scatter histogram: global scatter vs "
            "per-block partials vs shared accumulator",
            variants=(
                KernelVariant(
                    "naive",
                    lambda: histogram.hist_naive_spec(65536, 2048),
                    context=_hist_context,
                    note="every program scatters into the global bins",
                ),
                KernelVariant(
                    "partials",
                    lambda: histogram.hist_opt_spec(65536, 2048),
                    role="optimized",
                    note="per-block partial rows, coalesced stores",
                ),
                KernelVariant(
                    "scratch",
                    lambda: histogram.hist_opt2_spec(65536, 2048),
                    role="optimized",
                    note="shared scratch accumulator + single final store",
                ),
            ),
            sampler=_full,
        ),
        RegistryEntry(
            name="gramschm",
            summary="Gram-Schmidt kernel3: stride-N q column walk vs the "
            "transposed contiguous walk (paper §VI-B)",
            variants=(
                KernelVariant(
                    "naive",
                    lambda: gramschm.k3_naive_spec(512, 512, 512, k=3),
                    note="q read with stride NK: one warm word per tile",
                ),
                KernelVariant(
                    "opt",
                    lambda: gramschm.k3_opt_spec(512, 512, 512, k=3),
                    role="optimized",
                    note="qT read contiguously",
                ),
            ),
            sampler=_full,
            region_map=(("q", "qT"),),
        ),
        RegistryEntry(
            name="ttm",
            summary="PASTA TTM: per-program scratch partials (abuse) vs "
            "the fused register accumulation",
            variants=(
                KernelVariant(
                    "scratch",
                    lambda: ttm.ttm_scratch_spec(512, 8, 32),
                    note="Y_shr holds program-local partials: abuse",
                ),
                KernelVariant(
                    "fused",
                    lambda: ttm.ttm_fused_spec(512, 8, 32),
                    role="optimized",
                    note="accumulate in registers, drop the scratch",
                ),
            ),
            sampler=_full,
        ),
        RegistryEntry(
            name="cuszp",
            summary="cuSZp-style compression: one scalar per program "
            "parked in shared scratch",
            variants=(
                KernelVariant(
                    "like",
                    lambda: ttm.cuszp_like_spec(64),
                    note="exclusive-sum broadcast via scratch",
                ),
            ),
            sampler=_full,
        ),
        RegistryEntry(
            name="flash",
            summary="flash attention: Q/K/V streaming with VMEM "
            "accumulator (well-tiled reference)",
            variants=(
                KernelVariant(
                    "default",
                    lambda: flash.flash_spec(4, 1024, 1024, 128),
                ),
            ),
            sampler=_full,
        ),
        RegistryEntry(
            name="gmm",
            summary="grouped matmul (MoE expert dispatch): expert-indexed "
            "W fetches",
            variants=(
                KernelVariant(
                    "default",
                    lambda: gmm.gmm_spec(
                        1024, 512, 512, 8, gmm.expert_ids(8, 8)
                    ),
                ),
            ),
            sampler=_full,
        ),
        RegistryEntry(
            name="ssd",
            summary="Mamba SSD chunk scan: per-(head,chunk) state "
            "streaming",
            variants=(
                KernelVariant(
                    "chunk",
                    lambda: ssd.ssd_chunk_spec(4, 8, 128, 64, 64),
                ),
            ),
            sampler=_full,
        ),
        RegistryEntry(
            name="ragged_flash",
            summary="serving ragged flash attention: dense decode/prefill "
            "sweeps vs the EasyDeL-style block-skip over [starts, ends)",
            variants=(
                KernelVariant(
                    "decode",
                    lambda: ragged_flash.ragged_decode_spec(),
                    context=ragged_flash.ragged_context,
                    note="dense decode sweep: every KV block, every row",
                ),
                KernelVariant(
                    "decode-ragged",
                    lambda: ragged_flash.ragged_decode_ragged_spec(),
                    context=ragged_flash.ragged_context,
                    role="optimized",
                    note="scalar-prefetched bounds skip dead KV blocks",
                ),
                KernelVariant(
                    "prefill",
                    lambda: ragged_flash.ragged_prefill_spec(),
                    context=ragged_flash.ragged_context,
                    note="dense causal prefill sweep",
                ),
                KernelVariant(
                    "prefill-ragged",
                    lambda: ragged_flash.ragged_prefill_ragged_spec(),
                    context=ragged_flash.ragged_context,
                    role="optimized",
                    note="causal + ragged clamp on the KV walk",
                ),
            ),
            sampler=_full,
        ),
        RegistryEntry(
            name="paged_attn",
            summary="serving paged KV-cache attention: contiguous cache "
            "sweep vs the vLLM-style block-table page gather",
            variants=(
                KernelVariant(
                    "decode",
                    lambda: paged_attn.paged_decode_spec(),
                    context=paged_attn.paged_context,
                    note="contiguous per-row cache, dense slot sweep",
                ),
                KernelVariant(
                    "decode-paged",
                    lambda: paged_attn.paged_decode_paged_spec(),
                    context=paged_attn.paged_context,
                    role="optimized",
                    note="block-table gather, clamped to context_lens",
                ),
                KernelVariant(
                    "prefill",
                    lambda: paged_attn.paged_prefill_spec(),
                    context=paged_attn.paged_context,
                    note="dense causal sweep over the contiguous cache",
                ),
                KernelVariant(
                    "prefill-paged",
                    lambda: paged_attn.paged_prefill_paged_spec(),
                    context=paged_attn.paged_context,
                    role="optimized",
                    note="page gather + causal clamp",
                ),
            ),
            sampler=_full,
        ),
    )
}


def names() -> Tuple[str, ...]:
    """All registered kernel names, stable order."""
    return tuple(REGISTRY)


def get(name: str) -> RegistryEntry:
    """Look up a registry entry; raises KeyError with the known names.

    Families named ``model.<model>.<kind>`` are *model-derived*: they
    are synthesized on demand by ``repro.core.model_kernels.kernel_entry``
    from a model's layer layout, so everything that consumes a registry
    entry — ``cuthermo profile/lint/tune/check`` and ``ShardedCollector``
    workers rebuilding specs from source stamps — works on them without
    the static REGISTRY (or ``names()``, and hence ``tune --all``'s
    default scope) ever listing them.
    """
    if name.startswith("model."):
        # the one reference from the kernels up to the profiler's model
        # bridge, made at call time so no kernel import loads the model
        from repro.core.model_kernels import kernel_entry

        return kernel_entry(name)
    try:
        return REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown kernel {name!r}; known: {', '.join(REGISTRY)}"
        ) from None


def resolve(ref: str) -> Tuple[RegistryEntry, KernelVariant]:
    """Resolve a CLI-style ``name`` or ``name:variant`` reference."""
    name, _, variant = ref.partition(":")
    entry = get(name)
    return entry, entry.variant(variant or None)


def build(
    ref: str,
) -> Tuple[KernelSpec, Optional[Dict[str, np.ndarray]]]:
    """Resolve + build a profile-ready (spec, dynamic_context) pair.

    The returned spec is *source-stamped* with its canonical
    ``name:variant`` ref, which is what lets a ``ShardedCollector``
    worker rebuild the identical spec (and seeded context) in another
    process — the spec object itself holds index-map lambdas and cannot
    be pickled.  Deterministic: two ``build`` calls for the same ref
    produce specs that collect bit-identical traces.
    """
    entry, variant = resolve(ref)
    spec = dataclasses.replace(
        variant.spec(), source=f"{entry.name}:{variant.name}"
    )
    return spec, variant.dynamic_context()


__all__ = [
    "KernelVariant",
    "REGISTRY",
    "RegistryEntry",
    "build",
    "flash", "gemm", "get", "gmm", "gramschm", "histogram", "names", "ops",
    "paged_attn", "ragged_flash", "ref", "resolve", "spmv", "ssd", "ttm",
]
