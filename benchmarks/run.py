"""Benchmark aggregator: one section per paper table, on the CPU.

    PYTHONPATH=src python -m benchmarks.run

Prints ``name,us_per_call,derived`` CSV rows per bench, as required,
and writes the machine-readable records — ``BENCH_collect.json`` for
the collection benchmarks (throughput, wall times, shard count, git
sha) and ``BENCH_tune.json`` for the autotuner loop (per-family
speedups, candidates tried, trajectories) — so the BENCH_* trajectory
can be tracked across commits without scraping stdout.
"""

from __future__ import annotations

import sys


def main() -> None:
    from benchmarks import (
        bench_overhead,
        bench_patterns,
        bench_tune,
    )
    from repro.core.collector import ShardedCollector

    # ONE warm shard pool for the whole suite: the collect bench pays
    # the spawn+import cost once (and records it), the tune bench then
    # profiles its candidates on the same warm workers.  The workers'
    # JAX is held to the CPU, so this process alone may hold a chip.
    collector = ShardedCollector(bench_overhead.effective_workers(4))
    rows = []
    failed = []
    try:
        for name, runner in (
            ("patterns (paper Table I)", bench_patterns.run),
            # run_all = Table II + collection throughput +
            # sharded-vs-serial + collection cache; it also writes the
            # BENCH_collect.json record
            (
                "overhead (paper Table II)",
                lambda: bench_overhead.run_all(collector=collector),
            ),
            # closes the tuning loop per family on the same warm pool;
            # writes BENCH_tune.json
            (
                "autotuner (closed loop)",
                lambda: bench_tune.run_all(collector=collector),
            ),
        ):
            print(f"\n===== {name} =====")
            try:
                rows.extend(runner())
            except Exception as e:  # noqa: BLE001 — keep the suite going
                print(f"# FAILED: {e!r}")
                rows.append((name, 0.0, f"FAILED {e!r}"))
                failed.append(name)
    finally:
        collector.close()

    print("\n===== summary: name,us_per_call,derived =====")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    if failed:
        sys.exit(f"{len(failed)} benchmark section(s) failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
