#!/usr/bin/env python3
"""Readings that a cell's limit is set from, on the chip, in one process.

    python3 chipbench/readings.py --workload <cell> --seeds 1,2,3 [--out FILE]

For each seed: the run's set-up (``harness.build``: weights from the
seed, one ``Server`` at the cell's slots and cache length), then the
cell's waves in the run's order until the wave of the mix's longest
request has finished; then the run's sample of finished requests
(``harness.pick``) judged by the run's own comparison (``harness.check``)
against the cell's limit, two ways:

* ``program``: the widest gap by which a served token's reference logit
  lies below the reference's best, as a run compares it;
* ``control``: the same, for the token that the reference computed in
  float8 puts first at each of those positions.  ``control_correct``
  has to come out false.

One JSON line per seed on standard output (and appended to ``--out``).
The benchmark's own runs do not run this.
"""

import json
import os
import pathlib
import sys

CHECKOUT = pathlib.Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CHECKOUT / ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]
    from chipbench import cells

    cell = cells.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        row = read_seed(cell, seed, "fp8")
        print(json.dumps(row), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    return 0


def read_seed(cell, seed: int, control: str, require_tpu: bool = True) -> dict:
    from chipbench import harness, traffic

    s = harness.build(cell, seed, require_tpu)
    tr, config = cell.traffic, cell.config
    longest = (max(tr["prompt_buckets"]), max(tr["output_buckets"]))
    win = harness.Window(float("inf"))
    for plen, out_len, prompts in traffic.waves(tr, seed, config["vocab_size"]):
        harness.run_wave(s, tr, plen, out_len, prompts, win, float("inf"))
        if (plen, out_len) == longest:
            break
    harness.free_server(s)
    picks = harness.pick(win.requests, tr["check_requests"], seed)
    limit = cell.limits["logit_gap_max"]
    prog, n, prog_ok = harness.check(s, config, picks, tr, limit)
    ctl, _, ctl_ok = harness.check(s, config, picks, tr, limit, control=control)
    return {"workload": cell.name, "seed": seed, "waves": win.waves, "tokens": n, "limit": limit,
            "program": prog, "program_correct": prog_ok, "control": ctl, "control_correct": ctl_ok}


if __name__ == "__main__":
    sys.exit(main())
