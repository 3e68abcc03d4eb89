"""Level 3: distributed heat analysis of compiled HLO.

CUTHERMO stops at the SM boundary because GPU block->SM binding is
non-deterministic.  On TPU the inter-chip analogue IS deterministic —
shardings fix which devices touch which array regions, and collectives
are visible in the compiled module.  This walker extracts:

* per-collective byte counts (all-gather / all-reduce / reduce-scatter /
  all-to-all / collective-permute), sized from operand shapes,
* a *device heat map*: distinct-device counts per logical array, derived
  from replica groups (a replicated weight has temperature = group size:
  the paper's "hot" pattern lifted to chips),
* redundant-collective detection: the same operand collected twice
  (paper's hot-spot pattern at the fleet level).

All parsing is over ``lowered.as_text()`` / ``compiled.as_text()`` —
no execution, so it works for 512-device dry-run modules on CPU.
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1,
    "s4": 1,
    "u4": 1,
    "s8": 1,
    "u8": 1,
    "s16": 2,
    "u16": 2,
    "bf16": 2,
    "f16": 2,
    "s32": 4,
    "u32": 4,
    "f32": 4,
    "s64": 8,
    "u64": 8,
    "f64": 8,
    "f8e4m3fn": 1,
    "f8e5m2": 1,
}

COLLECTIVE_OPS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

# e.g.  f32[128,1024]{1,0}  or  bf16[2,16,16]
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
# a tuple shape's leaves carry TPU tiled layouts with their own parens,
# e.g.  (f32[8]{0:T(256)}, bf16[4,128]{1,0:T(8,128)(2,1)})
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*"
    r"((?:\((?:[^()]|\([^()]*\))*\))|(?:[a-z0-9]+\[[0-9,]*\][^ ]*))\s+"
    r"([a-z0-9\-]+)\(",
)
_REPLICA_GROUPS_RE = re.compile(r"replica_groups=\{(.*?)\}\s*(?:,|$)")
_REPLICA_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _parse_shape_bytes(shape_text: str) -> int:
    """Total bytes of a shape string; tuples sum their leaves."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _parse_group_size(line: str) -> int:
    m = _REPLICA_GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _REPLICA_GROUPS_RE.search(line)
    if m:
        first = m.group(1).split("},{")[0].strip("{}")
        if first:
            return len(first.split(","))
    return 1


@dataclasses.dataclass(frozen=True)
class CollectiveStats:
    """Byte accounting for one collective instruction."""

    op: str
    name: str
    out_bytes: int
    group_size: int

    @property
    def wire_bytes_per_device(self) -> float:
        """Bytes each device moves over ICI for this collective.

        Standard ring costs on a group of size g with full output B bytes:
          all-gather       (g-1)/g * B      (output is the gathered B)
          reduce-scatter   (g-1)/g * B      (input B reduced to B/g)
          all-reduce       2 (g-1)/g * B    (RS + AG)
          all-to-all       (g-1)/g * B
          collective-permute  B             (one hop)
        """
        g = max(1, self.group_size)
        b = self.out_bytes
        if self.op == "all-reduce":
            return 2.0 * (g - 1) / g * b
        if self.op == "collective-permute":
            return float(b)
        return (g - 1) / g * b


@dataclasses.dataclass
class HloHeat:
    """Distributed heat profile of one compiled module."""

    collectives: List[CollectiveStats] = dataclasses.field(default_factory=list)
    per_op_bytes: Dict[str, float] = dataclasses.field(
        default_factory=lambda: defaultdict(float)
    )
    redundant: List[Tuple[str, int]] = dataclasses.field(default_factory=list)

    @property
    def collective_bytes(self) -> float:
        """Total wire bytes per device (the roofline collective numerator)."""
        return sum(c.wire_bytes_per_device for c in self.collectives)

    @property
    def collective_count(self) -> int:
        return len(self.collectives)

    def bytes_by_op(self) -> Dict[str, float]:
        out: Dict[str, float] = defaultdict(float)
        for c in self.collectives:
            out[c.op] += c.wire_bytes_per_device
        return dict(out)

    def device_temperature(self) -> Dict[str, int]:
        """Distinct-device 'temperature' per collective (group sizes)."""
        return {c.name: c.group_size for c in self.collectives}

    def as_dict(self) -> Dict[str, object]:
        """JSON-ready summary (the v5 manifest's ``layers.hlo.heat``)."""
        return {
            "collective_count": self.collective_count,
            "collective_bytes": self.collective_bytes,
            "bytes_by_op": self.bytes_by_op(),
            "redundant": [[name, int(n)] for name, n in self.redundant],
        }


def analyze_hlo(hlo_text: str) -> HloHeat:
    """Walk an HLO module's text and accumulate collective heat."""
    heat = HloHeat()
    sig_seen: Dict[Tuple[str, str, int], int] = defaultdict(int)
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, shape_text, op = m.group(1), m.group(2), m.group(3)
        base_op = None
        for c in COLLECTIVE_OPS:
            if op == c or op.startswith(c + "-"):  # e.g. all-gather-start
                base_op = c
                break
        if base_op is None:
            continue
        if op.endswith("-done"):
            continue  # avoid double counting async pairs
        out_bytes = _parse_shape_bytes(shape_text)
        group = _parse_group_size(line)
        heat.collectives.append(
            CollectiveStats(op=base_op, name=name, out_bytes=out_bytes, group_size=group)
        )
        heat.per_op_bytes[base_op] += out_bytes
        sig = (base_op, shape_text, group)
        sig_seen[sig] += 1
    heat.redundant = [
        (f"{op} {shape}", count)
        for (op, shape, _g), count in sig_seen.items()
        if count > 1
    ]
    return heat


def memory_analysis_dict(compiled) -> Dict[str, float]:
    """Normalize compiled.memory_analysis() across backends."""
    ma = compiled.memory_analysis()
    out: Dict[str, float] = {}
    for key in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "generated_code_size_in_bytes",
        "alias_size_in_bytes",
    ):
        val = getattr(ma, key, None)
        if val is not None:
            out[key] = float(val)
    return out


def cost_analysis_dict(compiled) -> Dict[str, float]:
    """Normalize compiled.cost_analysis() (dict or list-of-dict)."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return {str(k): float(v) for k, v in dict(ca).items()}
