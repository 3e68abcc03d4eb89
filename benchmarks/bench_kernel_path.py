"""Deploy-path (Pallas-kernel) roofline estimate for the train cells.

The dry-run lowers attention as ``flash_xla`` (a CPU host cannot lower
TPU Pallas), whose per-chunk score chains stream f32 through the byte
model.  The Pallas kernel (`kernels/flash.py`, oracle-validated in
interpret mode) keeps scores/stats/accumulator in VMEM — that traffic
does not exist on the deployed path.

Measurement (not guesswork): the flash chunk loop is the only NESTED
scan in these train steps, so the attention-internal traffic is exactly
the byte tally of while bodies at depth >= 2.  This bench re-derives the
memory term with that tally removed:

    kernel_memory = hlo_bytes - depth2_bytes + qkv_streams

and reports which roofline side each train cell lands on when deployed
with the kernel.  Writes one row per arch; run AFTER the dry-run sweep.

    PYTHONPATH=src python -m benchmarks.bench_kernel_path --arch granite-8b
"""

from __future__ import annotations

import argparse
import json
import os
import re
from typing import List, Tuple

from repro.core.roofline import V5E, peaks_for

HBM_BW = peaks_for(V5E).hbm_bw
PEAK_FLOPS_BF16 = peaks_for(V5E).flops_bf16

ART = os.path.normpath(
    os.path.join(os.path.dirname(__file__), "..", "artifacts", "dryrun",
                 "single_16x16")
)
CHIPS = 256


def measure_depth2_bytes(arch: str) -> float:
    """Lower the cell and tally byte traffic inside nested while bodies."""
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")
    from repro.launch.mesh import make_production_mesh
    import repro.launch.dryrun as D
    from repro.core import hlo_cost
    from repro.parallel.context import use_rules

    mesh = make_production_mesh(multi_pod=False)
    fn, args, _, meta = D.build_cell(arch, "train_4k", mesh)
    rules = meta.pop("_rules")
    with mesh, use_rules(rules, mesh):
        co = fn.lower(*args).compile()
    model = hlo_cost.HloCostModel(co.as_text(), CHIPS)
    total = {"d2": 0.0}

    def walk(name, mult, depth):
        comp = model.comps.get(name)
        if comp is None:
            return
        for ins in comp.instrs:
            b = model._instr_cost(ins).bytes
            if ins.op in ("fusion", "call"):
                m = hlo_cost._CALL_ATTR_RE.search(ins.line)
                if m:
                    cal = m.group(1).replace("%", "").split(",")[0].strip()
                    if cal in model.comps:
                        b = model._fusion_bytes(ins, cal)
            if depth >= 2:
                total["d2"] += b * mult
            if ins.op == "while":
                mb = re.search(r"body=%?([\w.\-]+)", ins.line)
                mt = hlo_cost._TRIP_RE.search(ins.line)
                trips = int(mt.group(1)) if mt else 1
                if mb:
                    walk(mb.group(1), mult * trips, depth + 1)

    walk(next(n for n in model.comps if n.startswith("main")), 1.0, 0)
    return total["d2"]


def run(archs=None) -> List[Tuple[str, float, str]]:
    out = []
    archs = archs or ["granite-8b"]
    print("arch,xla_mem_ms,attn_internal_ms,kernel_mem_ms,compute_ms,"
          "collective_ms,xla_bound->kernel_bound,xla_mfu->kernel_mfu")
    for arch in archs:
        path = os.path.join(ART, f"{arch}__train_4k.json")
        if not os.path.exists(path):
            continue
        d = json.load(open(path))
        r = d["roofline"]
        d2 = measure_depth2_bytes(arch)
        mem_kernel = max(r["hlo_bytes"] - d2, 0.1 * r["hlo_bytes"]) / HBM_BW
        step0 = max(r["compute_s"], r["memory_s"], r["collective_s"])
        step1 = max(r["compute_s"], mem_kernel, r["collective_s"])
        b1 = max((("compute", r["compute_s"]), ("memory", mem_kernel),
                  ("collective", r["collective_s"])), key=lambda kv: kv[1])[0]
        mfu0 = d["model_flops"] / (step0 * CHIPS * PEAK_FLOPS_BF16)
        mfu1 = d["model_flops"] / (step1 * CHIPS * PEAK_FLOPS_BF16)
        print(f"{arch},{r['memory_s']*1e3:.0f},{d2/HBM_BW*1e3:.0f},"
              f"{mem_kernel*1e3:.0f},{r['compute_s']*1e3:.0f},"
              f"{r['collective_s']*1e3:.0f},{d['bound']}->{b1},"
              f"{100*mfu0:.1f}%->{100*mfu1:.1f}%")
        out.append((f"kernelpath_{arch}", step1 * 1e6,
                    f"{b1}-bound mfu={100*mfu1:.1f}%"))
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", action="append", default=None)
    a = ap.parse_args()
    run(a.arch)
