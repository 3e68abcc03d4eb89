"""Device time of the MoE layer in a traced window's decode ticks, for
the per-layer metrics that read it (``moe_device_ms.decode``,
``moe_roofline.decode``).

TPU op events carry no scope, so each is looked up in the compiled HLO of
the cell's decode step as ``attribute.py`` reads it: ``compiled_texts``
on a ``Server`` built from the configuration (``harness.program_model``)
with abstract weights, at the slots and cache length of the
configuration's cells, then ``scoped_ops``.  An operation is the MoE's
when its innermost layer scope is ``moe`` (the layer's norm and its
``moe/route``, ``moe/experts`` and ``moe/shared`` parts), or when it is
one of the grouped matmuls or what they read that the layer loop made
(``grouped_matmul_keys``).  A program without the scope gives nothing.
"""

from __future__ import annotations

import json
import re
import types
from typing import Dict, List, Optional, Set, Tuple

from chipbench import attribute as A
from chipbench import cells, harness
from chipbench import trace as T


def decode_seconds(ctx) -> Optional[Tuple[float, int]]:
    """(leaf-op device seconds of the MoE layers in the window's decode
    executions, their number), or None; read once per run's ``ctx``."""
    if "moe_scope.decode" not in ctx:
        ctx["moe_scope.decode"] = _moe_seconds(ctx)
    return ctx["moe_scope.decode"]


def _moe_seconds(ctx) -> Optional[Tuple[float, int]]:
    trace = ctx["trace"]
    runs = T.runs(trace, "jit_decode")
    if not runs or not trace.ops.get(0):
        return None
    texts = _decode_texts(ctx["config"], ctx["slots"])
    ops = A.scoped_ops(trace, texts)
    grouped = set().union(*map(grouped_matmul_keys, texts))
    moe = sum(e - s for (key, s, e, scope), _ in A.leaf_ops(trace, ops, "jit_decode")
              if A.layer(scope) == "moe" or key in grouped)
    return (moe, len(runs)) if moe > 0 else None


_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = ")


def grouped_matmul_keys(text: str) -> Set[str]:
    """Keys (as ``trace.op_name`` makes them) of one compiled HLO's grouped
    matmuls and of the layer loop's copies that they read.  The TPU
    compiler writes each ``ragged_dot`` as custom calls whose op_name is
    ``ragged-dot-...``, with no scope path, and copies each expert weight
    out of the loop's stacked weights (under the loop's scope, ``stack``)
    for them to read: those copies are the layer's weight reads."""
    lines = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            lines[m.group(1)] = line.strip().removeprefix("ROOT ")
    keys = set()
    for line in lines.values():
        if 'op_name="ragged-dot' not in line:
            continue
        keys.add(T.op_name(line))
        args = line.split("(", 1)[1].split("), ", 1)[0]
        for ref in re.findall(r"%([\w.\-]+)", args):
            meta = A.OP_NAME.search(lines.get(ref, ""))
            if meta and A.layer(meta.group(1)) == "stack":
                keys.add(T.op_name(lines[ref]))
    return keys


def _traffics(config, slots: int) -> List[dict]:
    """The traffic of each cell of ``config`` at ``slots``, one per cache length."""
    with open(cells.CHECKOUT / "BENCHMARK.json") as f:
        bench = json.load(f)
    out: Dict[int, dict] = {}
    for w in bench["workloads"]:
        if w["config"] == config["name"]:
            with open(cells.HERE / "traffic" / f"{w['traffic']}.json") as f:
                tr = json.load(f)
            if tr["slots"] == slots:
                out.setdefault(tr["max_seq"], tr)
    return list(out.values())


def _decode_texts(config, slots: int) -> List[str]:
    from repro.runtime import ServeConfig, Server

    model, pcfg = harness.program_model(config)
    texts = []
    for tr in _traffics(config, slots):
        srv = Server(model, model.abstract_params(),
                     ServeConfig(batch_slots=slots, max_seq=tr["max_seq"]), dtype=pcfg.dtype)
        s = types.SimpleNamespace(model=model, pcfg=pcfg, srv=srv)
        texts += A.compiled_texts(s, dict(tr, prompt_buckets=[]))  # the decode step only
        srv.caches = None
    return texts
