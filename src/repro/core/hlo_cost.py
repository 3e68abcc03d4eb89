"""Trip-count-aware HLO cost analysis (fixes XLA's single-count loops).

``compiled.cost_analysis()`` counts each while-loop BODY once, so a
61-layer scanned transformer reports ~1/61st of its FLOPs, and the
collectives inside the scan (per-layer FSDP all-gathers!) are similarly
under-counted.  This module re-derives costs from the compiled HLO text
with the call graph walked properly:

  * every computation's local cost = Σ dot FLOPs (2·|out|·|contraction|)
    + Σ elementwise/reduce byte traffic + collective wire bytes;
  * while bodies are multiplied by their trip count (parsed from the
    loop condition's comparison constant — exact for lax.scan loops);
  * fusions/calls/conditionals are followed once (max across branches).

Validated against ``cost_analysis`` on loop-free modules (tests).
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

import numpy as np

_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "u2": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e5m2fnuz": 1, "c128": 16,
    "token": 0, "opaque": 0,
}

COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_OPERANDS_RE = re.compile(r"\(([^()]*(?:\([^()]*\)[^()]*)*)\)")
_CALL_ATTR_RE = re.compile(
    r"(?:to_apply|calls|branch_computations|called_computations)=\{?%?([\w.\-, %]+)\}?"
)
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_CONST_RE = re.compile(r"constant\((\d+)\)")
_REPLICA_RE = re.compile(r"replica_groups=\{(.*?)\}\}?")
_REPLICA_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_DIM_LABELS_RE = re.compile(r"dim_labels=(\w+)_(\w+)->(\w+)")
_WINDOW_RE = re.compile(r"window=\{([^}]*)\}")


def _dims(shape_text: str) -> Optional[List[int]]:
    """Dimensions of the first array shape in ``shape_text``."""
    m = _SHAPE_RE.search(shape_text)
    return [int(d) for d in m.group(2).split(",") if d] if m else None


def _window_attrs(line: str) -> Tuple[List, ...]:
    """Per-spatial-dim (size, stride, (pad_lo, pad_hi), lhs_dilate,
    rhs_dilate) lists of a convolution's ``window={...}``."""
    m = _WINDOW_RE.search(line)
    attrs = dict(kv.split("=", 1) for kv in m.group(1).split()) if m else {}

    def ints(key: str) -> List[int]:
        return [int(v) for v in attrs[key].split("x")] if key in attrs else []

    pads = [
        tuple(int(v) for v in p.split("_"))
        for p in attrs.get("pad", "").split("x") if p
    ]
    return ints("size"), ints("stride"), pads, ints("lhs_dilate"), ints("rhs_dilate")


def _shape_elems_bytes(shape_text: str) -> Tuple[int, int]:
    """(elements, bytes) of a shape string (tuples sum their leaves)."""
    elems = 0
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        elems += n
        total += n * _DTYPE_BYTES[dtype]
    return elems, total


@dataclasses.dataclass
class Instr:
    name: str
    shape: str
    op: str
    line: str
    operands: List[str]
    root: bool = False


@dataclasses.dataclass
class Computation:
    name: str
    instrs: List[Instr] = dataclasses.field(default_factory=list)


def _parse_operands(line: str, op: str) -> List[str]:
    # find the argument list right after the op name
    idx = line.find(op + "(")
    if idx < 0:
        return []
    depth = 0
    args_text = ""
    for ch in line[idx + len(op):]:
        if ch == "(":
            depth += 1
            if depth == 1:
                continue
        if ch == ")":
            depth -= 1
            if depth == 0:
                break
        if depth >= 1:
            args_text += ch
    out = []
    for tok in args_text.split(","):
        tok = tok.strip().lstrip("%")
        # strip shape prefixes like "f32[8,16] %foo"
        parts = tok.split()
        if parts:
            out.append(parts[-1].lstrip("%"))
    return out


def _parse_instr_line(line: str) -> Optional[Tuple[str, str, str]]:
    """Returns (name, shape_text, op) or None.  Handles tuple shapes."""
    s = line.strip()
    if s.startswith("ROOT "):
        s = s[5:]
    if " = " not in s:
        return None
    name, rhs = s.split(" = ", 1)
    name = name.strip().lstrip("%")
    rhs = rhs.strip()
    if rhs.startswith("("):  # tuple shape: balance parens
        depth = 0
        end = 0
        for i, ch in enumerate(rhs):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        shape, rest = rhs[: end + 1], rhs[end + 1 :].strip()
    else:
        sp = rhs.find(" ")
        if sp < 0:
            return None
        shape, rest = rhs[:sp], rhs[sp + 1 :].strip()
    op = rest.split("(", 1)[0].strip()
    if not op or not re.fullmatch(r"[\w\-]+", op):
        return None
    return name, shape, op


def parse_module(text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    current: Optional[Computation] = None
    for raw in text.splitlines():
        line = raw.rstrip()
        if not line:
            continue
        stripped = line.strip()
        if line.endswith("{") and ("->" in line) and " = " not in stripped:
            name = stripped.split()[0].lstrip("%")
            if name == "ENTRY":
                name = stripped.split()[1].lstrip("%")
            current = Computation(name=name)
            comps[name] = current
            continue
        if stripped == "}":
            current = None
            continue
        if current is None:
            continue
        parsed = _parse_instr_line(line)
        if not parsed:
            continue
        name, shape, op = parsed
        current.instrs.append(
            Instr(name=name, shape=shape, op=op, line=line,
                  operands=_parse_operands(line, op),
                  root=stripped.startswith("ROOT "))
        )
    return comps


def _group_size(line: str, total_devices: int) -> int:
    m = _REPLICA_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _REPLICA_RE.search(line)
    if m:
        first = m.group(1).split("},{")[0].strip("{}")
        if first:
            return len(first.split(","))
    return max(1, total_devices)


@dataclasses.dataclass
class Cost:
    flops: float = 0.0
    bytes: float = 0.0
    wire_bytes: float = 0.0  # collective bytes per device
    by_collective: Dict[str, float] = dataclasses.field(
        default_factory=lambda: defaultdict(float)
    )

    def scaled(self, k: float) -> "Cost":
        c = Cost(self.flops * k, self.bytes * k, self.wire_bytes * k)
        for op, b in self.by_collective.items():
            c.by_collective[op] = b * k
        return c

    def add(self, other: "Cost") -> None:
        self.flops += other.flops
        self.bytes += other.bytes
        self.wire_bytes += other.wire_bytes
        for op, b in other.by_collective.items():
            self.by_collective[op] += b

    def as_dict(self) -> Dict[str, float]:
        """JSON-ready summary (the v5 manifest's ``layers.hlo.cost``)."""
        return {
            "flops": self.flops,
            "bytes": self.bytes,
            "wire_bytes": self.wire_bytes,
            "by_collective": dict(self.by_collective),
        }


# ops with negligible byte traffic (bookkeeping; while bodies account
# their own traffic — the while op's carried-tuple operands are not reads)
_SKIP_BYTES = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "iota", "partition-id", "replica-id", "while",
    "conditional",
}


class HloCostModel:
    def __init__(self, text: str, total_devices: int = 1):
        self.comps = parse_module(text)
        self.total_devices = total_devices
        # global name -> shape (instruction names are unique module-wide)
        self.shapes: Dict[str, str] = {}
        for comp in self.comps.values():
            for ins in comp.instrs:
                self.shapes[ins.name] = ins.shape
        self._memo: Dict[str, Cost] = {}
        self._const: Dict[str, int] = {}
        for comp in self.comps.values():
            for ins in comp.instrs:
                if ins.op == "constant":
                    m = _CONST_RE.search(ins.line)
                    if m:
                        self._const[ins.name] = int(m.group(1))

    # -- trip count -------------------------------------------------------

    def trip_count(self, cond_name: str) -> int:
        comp = self.comps.get(cond_name)
        if comp is None:
            return 1
        # the scan condition is compare(induction, constant(N)), LT
        best = 1
        for ins in comp.instrs:
            if ins.op == "compare":
                for opnd in ins.operands:
                    if opnd in self._const:
                        best = max(best, self._const[opnd])
                m = _CONST_RE.search(ins.line)
                if m:
                    best = max(best, int(m.group(1)))
        return best

    # -- per-instruction local cost ----------------------------------------

    def _instr_cost(self, ins: Instr) -> Cost:
        c = Cost()
        out_elems, out_bytes = _shape_elems_bytes(ins.shape)
        if ins.op == "dot":
            # FLOPs = 2 * |out| * contraction size
            m = _CONTRACT_RE.search(ins.line)
            contract = 1
            if m and ins.operands:
                lhs_shape = self.shapes.get(ins.operands[0], "")
                dims_txt = _SHAPE_RE.search(lhs_shape)
                if dims_txt:
                    dims = [int(d) for d in dims_txt.group(2).split(",") if d]
                    for di in (int(x) for x in m.group(1).split(",") if x):
                        if di < len(dims):
                            contract *= dims[di]
            c.flops += 2.0 * out_elems * contract
        elif ins.op == "convolution":
            c.flops += self._conv_flops(ins, out_elems)
        elif ins.op not in _SKIP_BYTES:
            # elementwise/reduce/etc: ~1 flop per output element
            c.flops += float(out_elems)
        # bytes: output + operands (approximation of HloCostAnalysis),
        # with slicing ops touching only their slice region
        if ins.op == "dynamic-slice":
            c.bytes += 2.0 * out_bytes
        elif ins.op == "dynamic-update-slice":
            upd = ins.operands[1] if len(ins.operands) > 1 else None
            c.bytes += 2.0 * _shape_elems_bytes(self.shapes.get(upd or "", ""))[1]
        elif ins.op not in _SKIP_BYTES:
            b = out_bytes
            for opnd in ins.operands:
                b += _shape_elems_bytes(self.shapes.get(opnd, ""))[1]
            c.bytes += b
        # collectives
        for coll in COLLECTIVES:
            if ins.op == coll or ins.op.startswith(coll + "-"):
                if ins.op.endswith("-done"):
                    break
                g = _group_size(ins.line, self.total_devices)
                if ins.op.startswith("all-reduce"):
                    wire = 2.0 * (g - 1) / g * out_bytes
                elif ins.op.startswith("collective-permute"):
                    wire = float(out_bytes)
                else:
                    wire = (g - 1) / g * out_bytes
                c.wire_bytes += wire
                c.by_collective[coll] += wire
                break
        return c

    def _conv_flops(self, ins: Instr, out_elems: int) -> float:
        """2 x multiply-adds of a convolution, padding taps excluded.

        The TPU backend writes most dots as convolutions
        (``dim_labels=0bf_oi0->0bf``): each output element contracts the
        kernel's input features times the window taps that land on real
        (not padded, not dilation-hole) input, as HloCostAnalysis counts.
        """
        labels = _DIM_LABELS_RE.search(ins.line)
        lhs_dims = _dims(self.shapes.get(ins.operands[0], "")) if ins.operands else None
        rhs_dims = (
            _dims(self.shapes.get(ins.operands[1], ""))
            if len(ins.operands) > 1 else None
        )
        out_dims = _dims(ins.shape)
        if not (labels and lhs_dims and rhs_dims and out_dims):
            return 2.0 * out_elems  # unparseable: count the outputs only
        lhs_l, rhs_l, out_l = labels.groups()
        window = _window_attrs(ins.line)
        taps = 1
        out_spatial = 1
        for d, ch in enumerate(c for c in out_l if c.isdigit()):
            n_in = lhs_dims[lhs_l.index(ch)]
            n_out = out_dims[out_l.index(ch)]
            out_spatial *= n_out
            size, stride, (lo, _hi), lhs_dil, rhs_dil = (
                w[d] if d < len(w) else dflt
                for w, dflt in zip(window, (1, 1, (0, 0), 1, 1))
            )
            o = np.arange(n_out)[:, None]
            k = np.arange(size)[None, :]
            pos = o * stride + k * rhs_dil - lo
            taps *= int(np.count_nonzero(
                (pos >= 0) & (pos <= (n_in - 1) * lhs_dil) & (pos % lhs_dil == 0)
            ))
        in_features = rhs_dims[rhs_l.index("i")]
        return 2.0 * (out_elems // max(1, out_spatial)) * in_features * taps

    # -- fusion byte model ---------------------------------------------------

    def _fusion_bytes(self, ins: Instr, callee: str) -> float:
        """HBM bytes a fusion actually touches.

        A loop fusion whose parameter is consumed ONLY by dynamic-slice
        reads just the slice (XLA fuses per-iteration slicing of stacked
        scan operands — counting the full buffer per trip over-counted
        granite-8b by ~50x).  In-place dynamic-update-slice writes only
        the update region.
        """
        comp = self.comps.get(callee)
        if comp is None:
            return self._plain_bytes(ins)
        param_idx: Dict[str, int] = {}
        for ci in comp.instrs:
            if ci.op == "parameter":
                m = re.search(r"parameter\((\d+)\)", ci.line)
                if m:
                    param_idx[ci.name] = int(m.group(1))
        consumers: Dict[str, List[Instr]] = {p: [] for p in param_idx}
        for ci in comp.instrs:
            if ci.op == "parameter":
                continue
            for o in ci.operands:
                if o in consumers:
                    consumers[o].append(ci)
        total = 0.0
        for pname, idx in param_idx.items():
            if idx >= len(ins.operands):
                continue
            full = _shape_elems_bytes(self.shapes.get(ins.operands[idx], ""))[1]
            cons = consumers.get(pname, [])
            if cons and all(c.op == "dynamic-slice" for c in cons):
                total += sum(_shape_elems_bytes(c.shape)[1] for c in cons)
            elif cons and all(
                c.op == "dynamic-update-slice" and c.operands
                and c.operands[0] == pname
                for c in cons
            ):
                # in-place target: the overwritten region, not the buffer
                for c in cons:
                    upd = c.operands[1] if len(c.operands) > 1 else None
                    total += _shape_elems_bytes(self.shapes.get(upd or "", ""))[1]
            else:
                total += full
        # output: a root DUS writes only its update region
        out_full = _shape_elems_bytes(ins.shape)[1]
        root = next((c for c in comp.instrs if c.root), None)
        if root is not None and root.op == "dynamic-update-slice" and len(root.operands) > 1:
            total += _shape_elems_bytes(self.shapes.get(root.operands[1], ""))[1]
        else:
            total += out_full
        return total

    def _plain_bytes(self, ins: Instr) -> float:
        b = _shape_elems_bytes(ins.shape)[1]
        for opnd in ins.operands:
            b += _shape_elems_bytes(self.shapes.get(opnd, ""))[1]
        return float(b)

    # -- call-graph walk --------------------------------------------------

    def comp_cost(self, name: str) -> Cost:
        """Full cost of a computation (while bodies x trip count)."""
        if name in self._memo:
            return self._memo[name]
        comp = self.comps.get(name)
        total = Cost()
        self._memo[name] = total  # cycle guard
        if comp is None:
            return total
        for ins in comp.instrs:
            c = self._instr_cost(ins)
            if ins.op in ("fusion", "call"):
                m = _CALL_ATTR_RE.search(ins.line)
                if m:
                    callee0 = m.group(1).replace("%", "").split(",")[0].strip()
                    if callee0 in self.comps:
                        c = Cost(flops=c.flops, wire_bytes=c.wire_bytes,
                                 bytes=self._fusion_bytes(ins, callee0))
            total.add(c)
            if ins.op == "while":
                body = cond = None
                mb = re.search(r"body=%?([\w.\-]+)", ins.line)
                mc = re.search(r"condition=%?([\w.\-]+)", ins.line)
                if mb:
                    body = mb.group(1)
                if mc:
                    cond = mc.group(1)
                mt = _TRIP_RE.search(ins.line)
                if mt:
                    trips = int(mt.group(1))
                else:
                    trips = self.trip_count(cond) if cond else 1
                if body:
                    total.add(self.comp_cost(body).scaled(trips))
                if cond:
                    total.add(self.comp_cost(cond).scaled(trips))
            elif ins.op in ("fusion", "call", "custom-call", "map", "reduce",
                            "reduce-window", "scatter", "sort",
                            "select-and-scatter"):
                m = _CALL_ATTR_RE.search(ins.line)
                if m:
                    for callee in m.group(1).replace("%", "").split(","):
                        callee = callee.strip()
                        if callee and callee in self.comps:
                            # fused internals: count FLOPs (the work is
                            # real) but not bytes (no HBM traffic — the
                            # fusion op itself already counted its
                            # params + output)
                            sub = self.comp_cost(callee)
                            total.add(Cost(flops=sub.flops,
                                           wire_bytes=sub.wire_bytes))
            elif ins.op == "conditional":
                m = _CALL_ATTR_RE.search(ins.line)
                if m:
                    branch_costs = [
                        self.comp_cost(c.strip())
                        for c in m.group(1).replace("%", "").split(",")
                        if c.strip() in self.comps
                    ]
                    if branch_costs:
                        best = max(branch_costs, key=lambda c: c.flops + c.bytes)
                        total.add(best)
        self._memo[name] = total
        return total

    def entry_cost(self) -> Cost:
        # ENTRY computation: the one named 'main' or the first parsed
        for cand in ("main",):
            if cand in self.comps:
                return self.comp_cost(cand)
        for name in self.comps:
            if name.startswith("main"):
                return self.comp_cost(name)
        first = next(iter(self.comps), None)
        return self.comp_cost(first) if first else Cost()


def analyze(text: str, total_devices: int = 1) -> Cost:
    return HloCostModel(text, total_devices).entry_cost()
