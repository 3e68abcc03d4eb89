"""Table I reproduction: patterns detected per application/kernel.

Paper (Table I): GEMM v00 -> A hot/false-shared, B false-shared; SpMV ->
rowOffsets misaligned + x hot-random; PASTA -> Y_shr abused SMEM;
GRAMSCHM -> q strided; cuSZp -> exel_sum/base_idx abused SMEM; GPUMD ->
cell_count strided/false-shared.

This bench runs the Level-1/2 profiler over the TPU-native analogue of
each kernel and reports (kernel, data object, detected pattern) rows —
the direct analogue of the paper's table.
"""

from __future__ import annotations

import time
from typing import List, Tuple

import numpy as np

from repro.core.collector import analyze
from repro.core.patterns import detect_all
from repro.core.trace import GridSampler
from repro.kernels.gemm import gemm_v00_spec, gemm_v01_spec, gemm_v02_spec
from repro.kernels.gramschm import k3_naive_spec, k3_opt_spec
from repro.kernels.histogram import hist_naive_spec, hist_opt2_spec
from repro.kernels.spmv import spmv_csr_spec, spmv_zigzag_spec
from repro.kernels.ttm import cuszp_like_spec, ttm_fused_spec, ttm_scratch_spec

# paper-faithful expectations per (app, kernel, object)
EXPECTED: List[Tuple[str, str, str, set]] = [
    ("GEMM", "gemm_v00", "B", {"hot", "false-sharing"}),
    ("GEMM", "gemm_v00", "C", {"false-sharing"}),
    ("GEMM", "gemm_v01", "B", {"hot"}),
    ("SpMV", "spmv_csr", "rowOffsets_shift1", {"misalignment"}),
    ("SpMV", "spmv_csr", "x", {"hot", "hot-random"}),
    ("PASTA", "spt_TTMRankRBNnzKernelSM", "Y_shr", {"scratch-abuse"}),
    ("cuSZp", "cuszp_compress_like", "exel_sum", {"scratch-abuse"}),
    ("cuSZp", "cuszp_compress_like", "base_idx", {"scratch-abuse"}),
    ("GRAMSCHM", "gramschmidt_kernel3", "q", {"strided"}),
    ("GPUMD", "find_cell_counts", "cell_count", {"hot", "false-sharing", "strided"}),
]


def run() -> List[Tuple[str, float, str]]:
    rng = np.random.default_rng(0)
    rows = []
    t0 = time.perf_counter()

    # GEMM
    hm00 = analyze(gemm_v00_spec(1024, 1024, 1024), GridSampler((0,), window=32))
    hm01 = analyze(gemm_v01_spec(1024, 1024, 1024), GridSampler((0,), window=32))
    hm02 = analyze(gemm_v02_spec(1024, 1024, 1024), GridSampler((0,), window=8))
    # SpMV: 36417x36417-ish matrix scale (paper footnote 2), zipf columns
    ncols = 36417
    colidx = np.minimum(
        rng.zipf(1.3, size=65536).astype(np.int64) * 37 % ncols, ncols - 1
    ).astype(np.int32)
    hm_spmv = analyze(
        spmv_csr_spec(65536, ncols), GridSampler((0,), window=32),
        dynamic_context={"col_indices": colidx},
    )
    hm_zig = analyze(
        spmv_zigzag_spec(65536, ncols), GridSampler((0,), window=32),
        dynamic_context={"col_indices": colidx},
    )
    # PASTA / cuSZp / GRAMSCHM / GPUMD
    hm_ttm = analyze(ttm_scratch_spec(512, 8, 32), GridSampler((0,), window=32))
    hm_ttm_f = analyze(ttm_fused_spec(512, 8, 32), GridSampler((0,), window=32))
    hm_cusz = analyze(cuszp_like_spec(64), GridSampler((0,), window=32))
    hm_gs = analyze(k3_naive_spec(512, 512, 512, k=3), GridSampler((0,), window=4))
    hm_gs_o = analyze(k3_opt_spec(512, 512, 512, k=3), GridSampler((0,), window=4))
    cells = rng.integers(0, 2048, size=65536).astype(np.int64)
    hm_gpumd = analyze(
        hist_naive_spec(65536, 2048), GridSampler((0,), window=32),
        dynamic_context={"cells": cells},
    )
    hm_gpumd_o = analyze(hist_opt2_spec(65536, 2048), GridSampler((0,), window=32))

    heatmaps = {
        "gemm_v00": hm00, "gemm_v01": hm01, "gemm_v02": hm02,
        "spmv_csr": hm_spmv, "spmv_zigzag": hm_zig,
        "spt_TTMRankRBNnzKernelSM": hm_ttm,
        "spt_TTMRankRBNnzKernel_reg": hm_ttm_f,
        "cuszp_compress_like": hm_cusz,
        "gramschmidt_kernel3": hm_gs, "gramschmidt_kernel3_opt": hm_gs_o,
        "find_cell_counts": hm_gpumd, "find_cell_counts_opt2": hm_gpumd_o,
    }
    detected: dict = {}
    for k, hm in heatmaps.items():
        detected[k] = {}
        for rep in detect_all(hm):
            detected[k].setdefault(rep.region, []).append(rep.pattern)

    dt = time.perf_counter() - t0
    hits = 0
    print("app,kernel,object,expected,detected,match")
    for app, kernel, obj, expect in EXPECTED:
        got = set(detected.get(kernel, {}).get(obj, []))
        ok = bool(got & expect)
        hits += ok
        print(f"{app},{kernel},{obj},{'|'.join(sorted(expect))},"
              f"{'|'.join(sorted(got)) or '-'},{'OK' if ok else 'MISS'}")
    # optimized variants must be clean of their original pattern
    clean = [
        ("gemm_v02", "C", "false-sharing"),
        ("spmv_zigzag", "rowPairs", "misalignment"),
        ("spt_TTMRankRBNnzKernel_reg", "Y_shr", "scratch-abuse"),
        ("gramschmidt_kernel3_opt", "qT", "strided"),
        ("find_cell_counts_opt2", "cell_count", "false-sharing"),
    ]
    for kernel, obj, pattern in clean:
        got = set(detected.get(kernel, {}).get(obj, []))
        ok = pattern not in got
        hits += ok
        print(f"(optimized),{kernel},{obj},no-{pattern},"
              f"{'|'.join(sorted(got)) or '-'},{'OK' if ok else 'MISS'}")
    total = len(EXPECTED) + len(clean)
    print(f"# pattern-table score: {hits}/{total} in {dt:.1f}s")
    return [("bench_patterns", dt * 1e6 / total, f"{hits}/{total}")]


if __name__ == "__main__":
    run()
