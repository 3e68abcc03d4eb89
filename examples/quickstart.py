"""Quickstart: the paper's tuning loop through the session API.

    PYTHONPATH=src python examples/quickstart.py

This is the paper's Fig. 2 workflow end to end on the GEMM case study —
profile -> heat map -> pattern -> fix -> re-profile — with every
iteration persisted to a session directory that the ``cuthermo`` CLI
(and any later process) can reload, re-render, and diff:

    cuthermo diff /tmp/cuthermo-quickstart/iter0 \
                  /tmp/cuthermo-quickstart/iter1
"""

import shutil

import jax
import jax.numpy as jnp

from repro.core import api
from repro.core.render import ReportEntry, render_ascii, write_report_bundle
from repro.core.session import ProfileSession
from repro.kernels import ops
from repro.kernels.gemm import gemm_v00_spec, gemm_v01_spec

SESS = "/tmp/cuthermo-quickstart"


def main() -> None:
    m = n = k = 1024
    shutil.rmtree(SESS, ignore_errors=True)
    sess = ProfileSession(SESS)

    print("== step 1: profile the naive kernel (gemm_v00) -> iter0 ==")
    it0 = sess.profile(
        [gemm_v00_spec(m, n, k)],
        names={"gemm_v00": "gemm"},
        variants={"gemm_v00": "v00"},
        note="baseline: one C row per program",
    )
    gemm0 = it0.kernel("gemm")
    print(api.format_report(gemm0.heatmap))
    print("\nheat map (first rows):")
    print(render_ascii(gemm0.heatmap, max_rows_per_region=4))

    print("== step 2: apply the top action (re-tile so one program owns "
          "whole (8,128) tiles) -> gemm_v01 -> iter1 ==")
    it1 = sess.profile(
        [gemm_v01_spec(m, n, k)],
        names={"gemm_v01": "gemm"},
        variants={"gemm_v01": "v01"},
        note="fix: whole C tiles per program",
    )

    print("== step 3: diff the iterations (the tuning-loop verdict) ==")
    sd = sess.diff(it0, it1)
    print(sd.summary())
    v = sd.verdicts[0]
    print(f"\nmodeled transfer speedup: {v.speedup_estimate:.1f}x "
          "(paper measured 7.2x cycle speedup for this fix)")

    print("\n== step 4: the kernels still agree ==")
    a = jax.random.normal(jax.random.key(0), (256, 256), jnp.float32)
    b = jax.random.normal(jax.random.key(1), (256, 256), jnp.float32)
    d0 = ops.matmul(a, b, variant="v00", interpret=True)
    d1 = ops.matmul(a, b, variant="v01", interpret=True)
    print("max |v00 - v01| =", float(jnp.abs(d0 - d1).max()))

    entries = [ReportEntry.from_profiled(pk) for pk in it1.kernels]
    written = write_report_bundle(entries, f"{SESS}/report",
                                  title="quickstart — iter1")
    print(f"\nsession persisted to {SESS} "
          f"(report bundle: {written['index.html']})")


if __name__ == "__main__":
    main()
