"""Rebuild the committed CI baseline artifact (artifacts/ci-baseline).

The ``check-smoke`` CI job gates every PR by profiling the benchmark
kernels fresh and running ``cuthermo check --baseline`` against the
iteration this script writes.  Profiling is deterministic integer
arithmetic over seeded contexts, so a freshly profiled candidate
matches the committed baseline exactly — any drift IS the signal the
gate exists to catch.

Regenerate (only after a deliberate change to the profiler's modeled
counts or the benchmark kernels) with::

    PYTHONPATH=src python tools/make_ci_baseline.py

then commit the updated ``artifacts/ci-baseline``.  The baseline uses
each family's *optimized* rung (``gemm:v01``, ``gramschm:opt``, and the
model-derived ``model.transformer-tiny.mlp:v02``) under the registry's
default sampler — the same spec/sampler the CI job profiles — stored
under the plain family names the check aligns on.
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from repro import kernels as kreg  # noqa: E402
from repro.core.session import profile_kernel, write_iteration  # noqa: E402

#: The baseline rungs: family name -> registry ref to profile.
BASELINE_REFS = {
    "gemm": "gemm:v01",
    "gramschm": "gramschm:opt",
    # a whole-model-derived family: the transformer-tiny FFN GEMM on its
    # blocked rung, synthesized by repro.core.model_kernels.kernel_entry.
    # Stored under the full family name — that is the name `cuthermo
    # profile --kernel model.transformer-tiny.mlp:v02` aligns on.
    "model.transformer-tiny.mlp": "model.transformer-tiny.mlp:v02",
}

OUT = Path(__file__).resolve().parent.parent / "artifacts" / "ci-baseline"


def main() -> int:
    profiled = []
    for name, ref in BASELINE_REFS.items():
        entry, variant = kreg.resolve(ref)
        spec, ctx = kreg.build(ref)
        pk = profile_kernel(
            spec,
            entry.sampler(),
            ctx,
            name=name,
            variant=variant.name,
            region_map=entry.region_map,
        )
        profiled.append(pk)
        print(
            f"profiled {ref} as {name!r}: {pk.transactions} transfers, "
            f"{len(pk.reports)} patterns",
            file=sys.stderr,
        )
    write_iteration(
        OUT,
        profiled,
        label="ci-baseline",
        note="committed baseline for the check-smoke CI gate "
        "(tools/make_ci_baseline.py)",
    )
    print(f"wrote {OUT}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
