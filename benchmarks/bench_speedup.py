"""Table III reproduction: optimization speedups guided by the heat map.

Two measurements per case study:
  * modeled HBM transaction ratio (the profiler's own currency — exact,
    hardware-independent), vs the paper's reported cycle speedups;
  * measured CPU wall time of the jit'd kernels where the variants do
    different real work (interpret-mode Pallas; directional only).

Paper Table III (A4500/RTX4090): gemm_v00 721.79%/682.82%, gemm_v01
26.07%/20.27%, SpMV 1.85%/1.97%, PASTA 163.56%/159.62%, GRAMSCHM k3
23.18%/19.81%.
"""

from __future__ import annotations

import time
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import analyze
from repro.core.trace import GridSampler
import repro.kernels.ops as ops
from repro.kernels.gemm import gemm_v00_spec, gemm_v01_spec, gemm_v02_spec
from repro.kernels.gramschm import k3_naive_block_spec, k3_opt_spec
from repro.kernels.histogram import hist_naive_spec, hist_opt2_spec
from repro.kernels.spmv import spmv_csr_spec, spmv_zigzag_spec
from repro.kernels.ttm import ttm_fused_spec, ttm_scratch_spec


def _time(fn, reps=3):
    fn()
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / reps


def run() -> List[Tuple[str, float, str]]:
    rng = np.random.default_rng(0)
    out = []
    print("case,tx_before,tx_after,modeled_speedup_pct,paper_pct,wall_before_s,wall_after_s")

    S = GridSampler((0,), window=32)
    rows = []

    # GEMM v00 -> v01 (paper: +721.79%).  The sampled windows produce
    # DIFFERENT amounts of C (32 rows vs 256 rows), so transactions are
    # normalized per produced C row (tx-per-unit-work == the cycle ratio).
    hm0 = analyze(gemm_v00_spec(1024, 1024, 1024), S)
    hm1 = analyze(gemm_v01_spec(1024, 1024, 1024), S)
    a = jax.random.normal(jax.random.key(0), (256, 256), jnp.float32)
    b = jax.random.normal(jax.random.key(1), (256, 256), jnp.float32)
    w0 = _time(lambda: ops.matmul(a, b, variant="v00", interpret=True))
    w1 = _time(lambda: ops.matmul(a, b, variant="v01", interpret=True))
    rows.append(("gemm_v00->v01",
                 hm0.sector_transactions() / 32,
                 hm1.sector_transactions() / 256, 721.79, w0, w1))

    # GEMM v01 -> v02 (paper: +26.07%; see EXPERIMENTS.md — on GPU the
    # gain was capped by a 99.2% L1 hit rate absorbing B re-fetches; TPU
    # has no data cache, so explicit tiling saves the full traffic)
    hm2 = analyze(gemm_v02_spec(1024, 1024, 1024), GridSampler(None))
    w2 = _time(lambda: ops.matmul(a, b, variant="v02", bm=64, bn=64, bk=64, interpret=True))
    rows.append(("gemm_v01->v02",
                 hm1.sector_transactions() / 256,
                 hm2.sector_transactions() / 1024, 26.07, w1, w2))

    # SpMV misaligned -> zigzag (paper: +1.85% whole-kernel — the offsets
    # are a small slice of total traffic; compare whole-kernel tx)
    colidx = rng.integers(0, 36417, size=65536).astype(np.int32)
    hm_s = analyze(spmv_csr_spec(65536, 36417), S,
                   dynamic_context={"col_indices": colidx})
    hm_z = analyze(spmv_zigzag_spec(65536, 36417), S,
                   dynamic_context={"col_indices": colidx})
    rows.append(("spmv_csr", hm_s.sector_transactions(),
                 hm_z.sector_transactions(), 1.85, None, None))

    # PASTA scratch -> registers (paper: +163.56%)
    tv = jax.random.normal(jax.random.key(2), (512, 8), jnp.float32)
    tu = jax.random.normal(jax.random.key(3), (512, 8, 32), jnp.float32)
    ws = _time(lambda: ops.ttm(tv, tu, use_scratch=True, interpret=True))
    wf = _time(lambda: ops.ttm(tv, tu, use_scratch=False, interpret=True))
    # scratch round-trip bytes modeled as the saved traffic
    hm_ts = analyze(ttm_scratch_spec(512, 8, 32), S)
    hm_tf = analyze(ttm_fused_spec(512, 8, 32), S)
    scratch_words = sum(
        sum(r.word_temps) for rh in hm_ts.regions
        if rh.region.space == "vmem_scratch" for r in rh.rows
    )
    rows.append(("pasta_ttm", hm_ts.sector_transactions() + scratch_words // 8,
                 hm_tf.sector_transactions(), 163.56, ws, wf))

    # GRAMSCHM k3 naive -> transposed (paper: +23.18%): whole-kernel tx
    # (q improves 64x but shares the kernel with the a/r streams)
    hm_g0 = analyze(k3_naive_block_spec(512, 512, 512, k=3), GridSampler(None))
    hm_g1 = analyze(k3_opt_spec(512, 512, 512, k=3), GridSampler(None))
    q = jax.random.normal(jax.random.key(4), (512, 512), jnp.float32)
    am = jax.random.normal(jax.random.key(5), (512, 512), jnp.float32)
    wg0 = _time(lambda: ops.gramschm_k3(q, am, k=3, naive=True, interpret=True))
    wg1 = _time(lambda: ops.gramschm_k3(q.T, am, k=3, naive=False, interpret=True))
    rows.append(("gramschm_k3", hm_g0.sector_transactions(),
                 hm_g1.sector_transactions(), 23.18, wg0, wg1))

    # GPUMD naive RMW -> scratch-accumulated (not in paper Table III:
    # "requires domain experts"; our TPU-native fix, reported forcompleteness)
    cells_np = rng.integers(0, 2048, size=65536).astype(np.int64)
    hm_h0 = analyze(hist_naive_spec(65536, 2048), GridSampler(None),
                    dynamic_context={"cells": cells_np})
    hm_h1 = analyze(hist_opt2_spec(65536, 2048), GridSampler(None))
    cells = jnp.asarray(cells_np, jnp.int32)
    wh0 = _time(lambda: ops.histogram(cells, 2048, naive=True, interpret=True))
    wh1 = _time(lambda: ops.histogram(cells, 2048, naive=False, interpret=True))
    rows.append(("gpumd_cells", hm_h0, hm_h1, None, wh0, wh1))

    for name, before, after, paper, wb, wa in rows:
        tx_b = before if isinstance(before, (int, float)) else before.sector_transactions()
        tx_a = after if isinstance(after, (int, float)) else after.sector_transactions()
        speed = 100.0 * (tx_b / max(tx_a, 1) - 1.0)
        print(f"{name},{tx_b},{tx_a},{speed:.1f}%,"
              f"{paper if paper is not None else '-'}%,"
              f"{wb if wb is not None else '-'},{wa if wa is not None else '-'}")
        out.append((f"speedup_{name}", 0.0,
                    f"modeled +{speed:.0f}% vs paper +{paper}%"))
    return out


if __name__ == "__main__":
    run()
