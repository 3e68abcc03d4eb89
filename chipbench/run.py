#!/usr/bin/env python3
"""Serving benchmark on the chip: one cell of ``BENCHMARK.json`` per run.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything runs in this one process, which holds the chip.  It finds the
cell's configuration and traffic by name (``chipbench/cells.py``), builds
the model through the program's own entry points, makes the weights from
the seed, warms up every program the cell's traffic uses (set-up), drives
closed-loop waves through ``repro.runtime.Server`` for ``--seconds``, and
checks a sample of what it served against the plain reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics read from a profiler trace of the
window's first waves), ``device``, ``breakdown`` (traced runs) and, last,
``checks``: each number compared with its limit.  The same comparison is
the last line of standard error.

It exits non-zero, and prints no result, when JAX finds no TPU or fewer
chips than the cell asks for.  JAX's persistent compilation cache is kept
at ``<checkout>/.jax_cache``, so only a checkout's first run compiles.
"""

import time

T0 = time.time()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

CHECKOUT = pathlib.Path(__file__).resolve().parent.parent


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CHECKOUT / ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    # libtpu writes its logs to /tmp/tpu_logs unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(), "tpu_logs"))
    sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]
    from chipbench import cells, harness

    cell = cells.load_cell(args.workload)
    try:
        result = harness.run(cell, args.seed, args.seconds, bool(args.trace), T0)
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
