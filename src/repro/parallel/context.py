"""Active-rules context: logical sharding constraints from inside model code.

Model code stays mesh-agnostic: it calls ``constrain_logical(x, names)``
with LOGICAL axis names; if a launcher has activated a rules table and
its mesh (via ``use_rules``), the call lowers to
``with_sharding_constraint`` — else it is a no-op (single-device tests,
interpret mode...).
"""

from __future__ import annotations

import contextlib
import contextvars
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding

from .sharding import Rules, fixup_specs

_ACTIVE: contextvars.ContextVar[Optional[Tuple[Rules, Mesh]]] = (
    contextvars.ContextVar("repro_active_rules", default=None)
)


@contextlib.contextmanager
def use_rules(rules: Rules, mesh: Mesh):
    """Activate ``rules`` over ``mesh`` for the model code in the block."""
    token = _ACTIVE.set((rules, mesh))
    try:
        yield rules
    finally:
        _ACTIVE.reset(token)


def active_rules() -> Optional[Rules]:
    active = _ACTIVE.get()
    return None if active is None else active[0]


def active_mesh() -> Optional[Mesh]:
    """The mesh of the enclosing ``use_rules`` block, if any."""
    active = _ACTIVE.get()
    return None if active is None else active[1]


def constrain_logical(x: jax.Array, logical_axes: Sequence[Optional[str]]):
    active = _ACTIVE.get()
    if active is None:
        return x
    rules, mesh = active
    spec = rules.spec(logical_axes)
    # drop mesh axes that don't divide the dim (shape-aware fixup)
    spec = fixup_specs(spec, jax.ShapeDtypeStruct(x.shape, x.dtype), mesh)
    # a bare PartitionSpec is rejected outside use_mesh contexts — always
    # bind it to the physical mesh (a silent fallback here cost 36 GiB of
    # replicated logits on whisper train_4k before this was explicit)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
