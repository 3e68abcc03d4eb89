"""Whole-model profiling: one iteration per model, per-layer attribution.

The microkernel registry profiles kernels in isolation; this module
profiles a *model* — every Pallas-modeled kernel its forward (and,
optionally, backward) pass invokes — into ONE session iteration whose
manifest carries per-layer attribution (artifact v5):

1. **Discovery.**  ``discover()`` walks the model's ``layout()`` layer
   by layer, block kind by block kind
   (:func:`repro.core.model_kernels.layer_kinds`), and builds each
   kernel's spec with :func:`repro.core.model_kernels.kind_spec` at the
   model's shapes, attributed to its layer.
2. **HLO-level sweep.**  The model forward (``value_and_grad`` of the
   loss when ``backward=True``) is jitted and compiled; the optimized
   HLO text runs through :mod:`repro.core.hlo_thermo` (collective /
   device-temperature heat) and :mod:`repro.core.hlo_cost` (flops /
   bytes / wire bytes), landing in the manifest's ``layers.hlo`` block.
3. **One iteration.**  Every discovered kernel is profiled through the
   standard :func:`repro.core.session.profile_kernel` assembly point
   (sharded collection and the content-addressed cache both apply) and
   persisted with a per-layer rollup table — validated on write as an
   exact partition, so per-layer transfer totals sum to the iteration
   total by construction.

Discovered kernels are stamped with ``model.<model>.<kind>`` family
refs (``repro.kernels.get`` delegates those to
``repro.core.model_kernels.kernel_entry``), which makes them first-class
tunable families: ``cuthermo tune model.transformer-tiny.mlp`` walks
the derived ladder, ``cuthermo lint``/``check`` accept the refs, and
sharded workers rebuild the specs from the stamps.

Backward kernels are a *model*: attention/GEMM backward passes stream
the same operand set with the data direction flipped (activations are
re-read, gradients written where inputs were read), so ``bwd_spec``
derives the backward footprint by swapping load/store kinds on the
forward spec — the standard first-order approximation of backward
memory traffic.
"""

from __future__ import annotations

import dataclasses
import json
import os
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp

from repro.core import hlo_cost, hlo_thermo
from repro.core.model_kernels import (
    KIND_RUNGS,
    apply_overrides,
    get_model,
    kind_spec,
    layer_kinds,
)
from repro.core.session import (
    Iteration,
    ProfileSession,
    ProfiledKernel,
    load_iteration,
    profile_kernel,
)
from repro.core.spec import KernelSpec
from repro.core.trace import GridSampler
from repro.fault import Preempted
from repro.models import build_model

__all__ = [
    "DiscoveredKernel",
    "MODEL_JOURNAL",
    "bwd_spec",
    "discover",
    "hlo_sweep",
    "iteration_transactions",
    "layers_table",
    "profile_model",
]

#: Name of the resumable-run journal ``profile_model`` keeps at the
#: session root while a whole-model profile is in flight.
MODEL_JOURNAL = "model.journal.json"


# ---------------------------------------------------------------------------
# discovery
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DiscoveredKernel:
    """One kernel of a model pass, attributed and profile-ready."""

    name: str  # manifest name: "layer0.attn", "head.unembed", "+ .bwd"
    layer: str  # layer path: "layer0" ... "head"
    kind: str  # 'attn' | 'mlp' | 'moe' | 'ssm' | 'unembed'
    family: str  # tunable family ref: "model.<model>.<kind>"
    spec: KernelSpec  # source-stamped (shard workers rebuild from it)
    backward: bool = False


def bwd_spec(cfg, kind: str, batch: int, seq: int, rung: int = 0) -> KernelSpec:
    """Backward-pass footprint of one derived kernel (kind-swapped).

    Loads become stores and vice versa (activations re-read as gradient
    writes, and the other way around); scratch accumulators are
    direction-free and stay put.  Importable at module scope so a
    ``ShardedCollector`` worker can rebuild the spec from its
    ``("repro.core.model_profile:bwd_spec", ...)`` source triple.
    """
    fwd = kind_spec(cfg, kind, batch, seq, rung=rung)
    flipped = {"load": "store", "store": "load"}
    operands = tuple(
        dataclasses.replace(op, kind=flipped.get(op.kind, op.kind))
        for op in fwd.operands
    )
    return dataclasses.replace(
        fwd, name=f"{fwd.name}_bwd", operands=operands
    )


def discover(
    model_name: str,
    cfg,
    batch: int,
    seq: int,
    backward: bool = False,
    *,
    default_shapes: bool = True,
) -> List[DiscoveredKernel]:
    """Walk one model pass and return its kernels with layer attribution.

    One kernel per (layer, kind) of :func:`layer_kinds`, its spec built
    by :func:`kind_spec` at the model's shapes.  ``backward=True``
    appends a ``.bwd`` (kind-swapped) kernel per forward kernel.  Specs
    are source-stamped for shard rebuild: with the registry's
    ``model.…:<rung>`` string ref when the config and shapes are the
    registry defaults (``default_shapes``), otherwise with a picklable
    builder triple.
    """
    discovered: List[DiscoveredKernel] = []
    for path, kind in layer_kinds(cfg):
        rung_name = KIND_RUNGS[kind][0][0]
        if default_shapes:
            source: object = f"model.{model_name}.{kind}:{rung_name}"
        else:
            source = (
                "repro.core.model_kernels:kind_spec",
                (cfg, kind, batch, seq),
                {"rung": 0},
            )
        spec = kind_spec(cfg, kind, batch, seq)
        discovered.append(
            DiscoveredKernel(
                name=f"{path}.{kind}",
                layer=path,
                kind=kind,
                family=f"model.{model_name}.{kind}",
                spec=dataclasses.replace(spec, source=source),
            )
        )
    if backward:
        for d in list(discovered):
            spec = bwd_spec(cfg, d.kind, batch, seq)
            discovered.append(
                dataclasses.replace(
                    d,
                    name=f"{d.name}.bwd",
                    spec=dataclasses.replace(
                        spec,
                        source=(
                            "repro.core.model_profile:bwd_spec",
                            (cfg, d.kind, batch, seq),
                            {"rung": 0},
                        ),
                    ),
                    backward=True,
                )
            )
    return discovered


# ---------------------------------------------------------------------------
# the HLO-level sweep
# ---------------------------------------------------------------------------


def hlo_sweep(cfg, batch: int, seq: int, backward: bool = False) -> Dict:
    """Compile the model pass and heat-profile its optimized HLO.

    Jits the forward (or the loss's ``value_and_grad`` when
    ``backward``) over abstract parameters, compiles, and runs the HLO
    text through :func:`repro.core.hlo_thermo.analyze_hlo` and
    :func:`repro.core.hlo_cost.analyze`.  Returns the JSON-ready
    ``layers.hlo`` manifest block.
    """
    model = build_model(cfg)
    params = model.abstract_params()
    toks = jax.ShapeDtypeStruct((batch, seq), jnp.int32)

    if backward:
        labels = jax.ShapeDtypeStruct((batch, seq), jnp.int32)

        def entry(p, t, y):
            def scalar_loss(pp):
                loss, _aux = model.loss(pp, t, y)
                return loss

            return jax.value_and_grad(scalar_loss)(p)

        lowered = jax.jit(entry).lower(params, toks, labels)
    else:

        def entry(p, t):
            logits, _, _ = model.apply(p, t)
            return logits

        lowered = jax.jit(entry).lower(params, toks)
    text = lowered.compile().as_text()
    heat = hlo_thermo.analyze_hlo(text)
    cost = hlo_cost.analyze(text)
    return {
        "backward": bool(backward),
        "heat": heat.as_dict(),
        "cost": cost.as_dict(),
    }


# ---------------------------------------------------------------------------
# rollup + the profile entry point
# ---------------------------------------------------------------------------


def layers_table(
    discovered: Sequence[DiscoveredKernel],
    profiled: Sequence[ProfiledKernel],
) -> List[Dict]:
    """Roll profiled kernels up into the v5 per-layer table.

    One row per layer path, in first-seen order; each row's
    ``transactions`` is the sum over its member kernels (the partition
    invariant ``session._validate_layers`` re-checks on write).
    """
    by_name = {pk.name: pk for pk in profiled}
    rows: Dict[str, Dict] = {}
    for d in discovered:
        pk = by_name[d.name]
        row = rows.setdefault(
            d.layer,
            {
                "path": d.layer,
                "kinds": [],
                "kernels": [],
                "transactions": 0,
                "patterns": [],
            },
        )
        if d.kind not in row["kinds"]:
            row["kinds"].append(d.kind)
        row["kernels"].append(d.name)
        row["transactions"] += pk.transactions
        for r in pk.reports:
            rd = r.as_dict()
            row["patterns"].append(
                [d.name, str(rd.get("region", "")), str(rd.get("pattern", ""))]
            )
    return list(rows.values())


def iteration_transactions(it: Iteration) -> int:
    """Total modeled transfers across an iteration's kernels."""
    return sum(pk.transactions for pk in it.kernels)


def _commit_journal(path: Path, journal: Dict) -> None:
    """Atomically (re)write the model-run journal (temp + rename)."""
    tmp = path.with_name(path.name + ".tmp")
    tmp.write_text(json.dumps(journal, indent=2) + "\n")
    os.replace(tmp, path)


def _load_partial(sess: ProfileSession, name: str, overrides, backward):
    """Validate a resume journal and load its partial iteration's kernels.

    Returns ``{kernel name: ProfiledKernel}`` of the work the preempted
    run already flushed (empty when it was preempted before any kernel
    finished).  Raises ``ValueError`` — the CLI's exit-2 class — when
    there is nothing to resume or the journaled run does not match the
    requested one (resuming a different model would silently splice
    foreign heat maps into the iteration).
    """
    jpath = sess.root / MODEL_JOURNAL
    if not jpath.is_file():
        raise ValueError(
            f"{sess.root}: nothing to resume (no {MODEL_JOURNAL}; the "
            "previous run either completed or never started)"
        )
    try:
        journal = json.loads(jpath.read_text())
    except (OSError, json.JSONDecodeError) as e:
        raise ValueError(f"{jpath}: unreadable model journal ({e})") from e
    want = {"model": name, "overrides": list(overrides),
            "backward": bool(backward)}
    got = {k: journal.get(k) for k in want}
    if journal.get("format") != "cuthermo-model-journal" or got != want:
        raise ValueError(
            f"{jpath}: journaled run {got} does not match the requested "
            f"run {want}; re-run without --resume to start over"
        )
    partial = journal.get("partial")
    if not partial:
        return {}
    it = load_iteration(sess.root / partial)
    return {pk.name: pk for pk in it.kernels}


def profile_model(
    name: str,
    out: Union[str, Path],
    *,
    overrides: Sequence[str] = (),
    backward: bool = False,
    sampler: Optional[GridSampler] = None,
    workers: int = 1,
    cache: Union[None, str, Path] = None,
    label: Optional[str] = None,
    note: str = "",
    hlo: bool = True,
    fault_plan=None,
    preemption=None,
    resume: bool = False,
) -> Iteration:
    """Profile one registered model into a session iteration (v5 artifact).

    Discovers the model's kernels per layer (:func:`discover`), profiles
    each through the standard assembly point — sharded collection
    (``workers``) and the content-addressed collection cache (``cache``)
    both apply — runs the HLO sweep, and persists everything as the next
    iteration of the session at ``out`` with the validated per-layer
    attribution table.  Returns the loaded :class:`Iteration` (its
    ``.layers`` carries the table).

    The run is preemption-safe: a journal (:data:`MODEL_JOURNAL`) lives
    at the session root while the profile is in flight, and when
    ``preemption`` (e.g. a :class:`repro.fault.PreemptionHandler`)
    reports ``requested`` between kernels, the kernels profiled so far
    are flushed as an emergency *partial* iteration, the journal records
    it, and :class:`~repro.fault.Preempted` is raised.
    ``resume=True`` picks such a run back up: the journal is validated
    against the requested arguments, the partial iteration's kernels are
    reused verbatim, and only the remainder is profiled — the final
    iteration is identical to an uninterrupted run's (heat-map writes
    are byte-deterministic).  ``fault_plan`` threads deterministic
    fault injection into the sharded collectors (``--inject-faults``).

    Raises ``KeyError`` for an unknown model and ``ValueError`` for a
    malformed ``--config`` override or an invalid resume (the CLI maps
    both to exit 2).
    """
    entry = get_model(name)
    cfg = apply_overrides(entry.config, overrides)
    batch, seq = entry.batch, entry.seq
    default_shapes = not overrides
    discovered = discover(
        name, cfg, batch, seq, backward=backward,
        default_shapes=default_shapes,
    )
    with ProfileSession(
        out, workers=workers, cache=cache, fault_plan=fault_plan
    ) as sess:
        done: Dict[str, ProfiledKernel] = (
            _load_partial(sess, name, overrides, backward) if resume else {}
        )
        journal: Dict[str, object] = {
            "format": "cuthermo-model-journal",
            "version": 1,
            "model": name,
            "overrides": list(overrides),
            "backward": bool(backward),
            "partial": None,
        }
        jpath = sess.root / MODEL_JOURNAL
        _commit_journal(jpath, journal)
        collector = sess.collector()
        profiled: List[ProfiledKernel] = []
        for d in discovered:
            if d.name in done:
                profiled.append(done[d.name])
                continue
            if preemption is not None and getattr(
                preemption, "requested", False
            ):
                # flush what we have as an emergency partial iteration so
                # --resume only pays for the remainder
                if profiled:
                    it = sess.add_iteration(
                        profiled,
                        label=f"model-{name}-partial",
                        note=(
                            f"preempted after {len(profiled)}/"
                            f"{len(discovered)} kernels; resumable"
                        ),
                    )
                    journal["partial"] = it.path.name
                    _commit_journal(jpath, journal)
                raise Preempted(
                    f"model profile of {name} preempted after "
                    f"{len(profiled)}/{len(discovered)} kernels; "
                    "resume with --resume"
                )
            profiled.append(
                profile_kernel(
                    d.spec,
                    sampler or GridSampler(None),
                    None,
                    name=d.name,
                    variant=f"{d.family}:{'bwd' if d.backward else 'fwd'}",
                    collector=collector,
                    cache=sess.cache,
                )
            )
        layers: Dict[str, object] = {
            "model": name,
            "batch": batch,
            "seq": seq,
            "overrides": list(overrides),
            "table": layers_table(discovered, profiled),
        }
        if hlo:
            layers["hlo"] = hlo_sweep(cfg, batch, seq, backward=backward)
        it = sess.add_iteration(
            profiled,
            label=label or f"model-{name}",
            note=note or f"whole-model profile of {name}",
            layers=layers,
        )
        jpath.unlink(missing_ok=True)
        return it
