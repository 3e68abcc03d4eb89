#!/usr/bin/env python3
"""Compile-only rehearsal of each cell for one chip of a described TPU v5e.

    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py [cell ...]

No chip is needed: the TPU compiler compiles for a chip that is described
and not attached.  For each cell (all of ``BENCHMARK.json`` by default) it
compiles ``Server``'s prefill at every prompt bucket and its decode, at
the cell's slots and ``max_seq``, and the plain reference at the check's
shapes; prints each program's ``memory_analysis``; and reckons the peak of
a run: the weights, the live cache, and the prefill's fresh cache, its
outputs and its scratch, which is when a run holds the most.
"""

from __future__ import annotations

import os
import pathlib
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
CHECKOUT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]

GB = 1e9


def nbytes(tree) -> int:
    import jax

    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def rehearse(name: str, one_chip) -> None:
    import jax
    import jax.numpy as jnp

    from chipbench import cells, harness

    cell = cells.load_cell(name)
    tr = cell.traffic
    model, pcfg = harness.program_model(cell.config)

    def place(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), tree)

    params = place(model.abstract_params())
    caches = place(model.init_caches(tr["slots"], tr["max_seq"], dtype=pcfg.dtype, abstract=True))
    w, c = nbytes(params), nbytes(caches)
    print(f"[{name}] weights {w / GB:.3f} GB, one cache tree {c / GB:.3f} GB "
          f"({tr['slots']} slots x {tr['max_seq']})", flush=True)

    def prefill(p, t, cc):
        return model.prefill(p, t, cc)

    def decode(p, t, cc):
        return model.decode_step(p, t, cc)

    worst = 0.0
    shapes = [("prefill", prefill, plen) for plen in tr["prompt_buckets"]] + [("decode", decode, 1)]
    for kind, fn, s in shapes:
        toks = jax.ShapeDtypeStruct((tr["slots"], s), jnp.int32, sharding=one_chip)
        mem = jax.jit(fn).lower(params, toks, caches).compile().memory_analysis()
        # held at once: weights, the live cache, the call's arguments
        # (a fresh cache for prefill, the live one for decode), its outputs
        # and scratch
        fresh = c if kind == "prefill" else 0
        total = w + c + fresh + mem.output_size_in_bytes + mem.temp_size_in_bytes
        worst = max(worst, total)
        print(f"[{name}] {kind} {tr['slots']}x{s}: arguments {mem.argument_size_in_bytes / GB:.3f} GB, "
              f"outputs {mem.output_size_in_bytes / GB:.3f} GB, scratch {mem.temp_size_in_bytes / GB:.3f} GB; "
              f"held {total / GB:.3f} GB", flush=True)

    family = cells.family_module(cell.config["family"])
    seq = jax.ShapeDtypeStruct((tr["max_seq"],), jnp.int32, sharding=one_chip)
    rows = jax.ShapeDtypeStruct((max(tr["output_buckets"]),), jnp.int32, sharding=one_chip)
    mem = family.logits_fn(cell.config).lower(params, seq, rows).compile().memory_analysis()
    print(f"[{name}] reference 1x{tr['max_seq']}: scratch {mem.temp_size_in_bytes / GB:.3f} GB; "
          f"held with the weights {(w + mem.temp_size_in_bytes) / GB:.3f} GB", flush=True)
    print(f"[{name}] reckoned peak {worst / GB:.3f} GB of 16", flush=True)


def main(argv=None) -> int:
    import json

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    names = list(argv if argv is not None else sys.argv[1:])
    if not names:
        with open(CHECKOUT / "BENCHMARK.json") as f:
            names = [w["name"] for w in json.load(f)["workloads"]]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    for name in names:
        rehearse(name, one_chip)
    return 0


if __name__ == "__main__":
    sys.exit(main())
