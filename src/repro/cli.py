"""``cuthermo`` — the command-line front end of the profiling loop.

Subcommands (see ``docs/cli.md`` for transcripts):

* ``cuthermo kernels`` — list the registered case-study kernels and
  their optimization-ladder variants (``--lint`` adds each variant's
  static verdict).
* ``cuthermo lint gemm:v00`` — static heat-map prediction: probe each
  operand's index map for an affine model and predict inefficiency
  patterns (plus spec bugs like out-of-bounds origins) without running
  or tracing anything; exits 0 clean / 1 findings / 2 usage error,
  ``--strict`` promotes warnings to failures.
* ``cuthermo profile --kernel gemm --out sess/`` — profile one or more
  kernels into the next iteration of a session directory.
* ``cuthermo model transformer-tiny --out sess/`` — whole-model
  profiling: discover every Pallas kernel a registered model's forward
  (and, with ``--backward``, backward) pass launches, profile them all
  into ONE iteration with per-layer attribution (artifact v5), and run
  the HLO-level sweep (collective heat + flop/byte cost) over the
  compiled module.  ``--config KEY=VALUE`` overrides config fields;
  ``--max-transfers N`` turns the iteration total into a CI budget
  (exit 1 when blown); exit 2 on unknown models / bad overrides.
* ``cuthermo report sess/iter0`` — rebuild the report bundle (HTML
  gallery + markdown digest + CSVs) for a stored iteration.
* ``cuthermo diff sess/iter0 sess/iter1`` — align two iterations and
  print per-kernel improved/regressed/fixed-pattern verdicts.
* ``cuthermo check sess/ --baseline artifacts/ci-baseline`` — the
  regression gate: evaluate a candidate iteration against a baseline
  artifact under configurable thresholds and/or scan a session's own
  rolling history for anomalies (``--anomaly``), emit a
  schema-versioned JSON report, and exit 0 (pass) / 1 (gate failure) /
  2 (usage or load error).  ``--static`` gates two *registry refs*
  on their lint reports instead — no traces, no artifacts.
* ``cuthermo tune gemm --out sess/`` — close the loop unattended: map
  advisor actions to candidate variants, re-profile, keep improvements,
  repeat until the patterns are fixed or the budget runs out.
  Candidates the static linter prices as strictly worse than the
  incumbent are skipped before any trace (``--no-prescreen`` disables;
  skips are recorded as ``static_skipped`` provenance).
* ``cuthermo tune --all --budget 16`` — the concurrent scheduler: tune
  every family (or a listed subset) together on one shared worker pool
  under one global budget, deterministic per ``--seed``.  ``--cache
  DIR`` (profile and tune) serves unchanged specs bit-identical heat
  maps from a content-addressed on-disk cache instead of re-tracing.

Heavy imports (numpy, jax-backed kernel modules) happen inside the
subcommand handlers, so ``cuthermo --help`` stays instant.
"""

from __future__ import annotations

import argparse
import sys
from typing import List, Optional


def _build_parser() -> argparse.ArgumentParser:
    """Construct the argparse tree for every subcommand."""
    p = argparse.ArgumentParser(
        prog="cuthermo",
        description="TPU memory heat-map profiler (CUTHERMO reproduction): "
        "profile Pallas kernels, detect inefficiency patterns, and track "
        "tuning iterations.",
    )
    sub = p.add_subparsers(dest="command", metavar="command")

    k = sub.add_parser(
        "kernels", help="list registered kernels and their variants"
    )
    k.add_argument(
        "--lint",
        action="store_true",
        help="add each variant's static lint verdict (clean/dirty/error) "
        "and predicted pattern classes — no kernels are run",
    )
    k.set_defaults(func=_cmd_kernels)

    ln = sub.add_parser(
        "lint",
        help="statically predict heat-map inefficiencies from specs "
        "alone (no runs, no traces; exit 0 clean / 1 findings / 2 error)",
    )
    ln.add_argument(
        "ref",
        nargs="*",
        metavar="NAME[:VARIANT]",
        help="registry refs to lint ('gemm' lints the baseline variant)",
    )
    ln.add_argument(
        "--all", action="store_true",
        help="lint every variant of every registered kernel",
    )
    ln.add_argument(
        "--strict",
        action="store_true",
        help="promote warning-level findings to failures (exit 1); "
        "error-level findings always fail",
    )
    ln.add_argument(
        "--json",
        default=None,
        metavar="PATH",
        help="write the schema-versioned JSON lint document to PATH "
        "('-' for stdout; the human summary then moves to stderr)",
    )
    ln.add_argument(
        "--quiet", "-q", action="store_true",
        help="suppress the human summary (exit code + JSON only)",
    )
    ln.set_defaults(func=_cmd_lint)

    pr = sub.add_parser(
        "profile",
        help="profile kernels into the next iteration of a session",
    )
    pr.add_argument(
        "--kernel",
        "-k",
        action="append",
        default=[],
        metavar="NAME[:VARIANT]",
        help="kernel to profile (repeatable); 'gemm' uses the baseline "
        "variant, 'gemm:v01' a specific one",
    )
    pr.add_argument(
        "--all", action="store_true", help="profile every registered kernel"
    )
    pr.add_argument(
        "--out",
        "-o",
        default="cuthermo-session",
        metavar="DIR",
        help="session directory (created on first use; default: "
        "./cuthermo-session)",
    )
    pr.add_argument(
        "--sampler",
        default=None,
        metavar="SPEC",
        help="grid sampler: 'full', or 'window:N' (pin the leading grid "
        "coordinate, admit N programs); default: per-kernel registry choice",
    )
    pr.add_argument(
        "--workers",
        "-w",
        type=int,
        default=1,
        metavar="N",
        help="shard collection across N worker processes (default: 1, "
        "serial); results are bit-identical for traces within the "
        "record cap, artifacts gain per-shard provenance",
    )
    pr.add_argument(
        "--cache",
        default=None,
        metavar="DIR",
        help="content-addressed collection cache directory: unchanged "
        "kernels return bit-identical stored heat maps instead of "
        "re-tracing (created on first use)",
    )
    pr.add_argument("--label", default=None, help="iteration label")
    pr.add_argument("--note", default="", help="free-form iteration note")
    pr.add_argument(
        "--inject-faults",
        default=None,
        metavar="SPEC",
        help="deterministically inject faults into sharded collection "
        "(e.g. 'seed=7' or 'seed=7,timeouts=0'); recovery is recorded "
        "as FaultEvent provenance and the heat maps stay bit-identical "
        "to a clean run",
    )
    pr.add_argument(
        "--quiet", "-q", action="store_true",
        help="suppress per-kernel text reports",
    )
    pr.set_defaults(func=_cmd_profile)

    mo = sub.add_parser(
        "model",
        help="whole-model profiling: discover and profile every kernel "
        "of a registered model into one per-layer-attributed iteration",
    )
    mo.add_argument(
        "name",
        nargs="?",
        default=None,
        metavar="NAME",
        help="registered model (see `cuthermo model --list`): "
        "transformer-tiny, moe-tiny, mamba-tiny",
    )
    mo.add_argument(
        "--list",
        action="store_true",
        help="list registered models and exit",
    )
    mo.add_argument(
        "--config",
        "-c",
        action="append",
        default=[],
        metavar="KEY=VALUE",
        help="override a model config field (repeatable), e.g. "
        "-c n_layers=4 -c d_ff=512; unknown keys exit 2",
    )
    mo.add_argument(
        "--backward",
        action="store_true",
        help="also profile the backward-pass kernels (store-heavy "
        "mirrors of each forward kernel) and sweep the grad HLO",
    )
    mo.add_argument(
        "--workers",
        "-w",
        type=int,
        default=1,
        metavar="N",
        help="shard collection across N worker processes (default: 1)",
    )
    mo.add_argument(
        "--cache",
        default=None,
        metavar="DIR",
        help="content-addressed collection cache directory: an "
        "unchanged model re-profiles bit-identically without re-tracing",
    )
    mo.add_argument(
        "--out",
        "-o",
        default="cuthermo-session",
        metavar="DIR",
        help="session directory (created on first use; default: "
        "./cuthermo-session)",
    )
    mo.add_argument(
        "--sampler",
        default=None,
        metavar="SPEC",
        help="grid sampler override for every discovered kernel: "
        "'full' or 'window:N' (default: full)",
    )
    mo.add_argument(
        "--max-transfers",
        type=int,
        default=None,
        metavar="N",
        help="CI budget: exit 1 when the iteration's total tile "
        "transfers exceed N",
    )
    mo.add_argument(
        "--no-hlo",
        action="store_true",
        help="skip the HLO-level sweep (no model compile; per-layer "
        "table only)",
    )
    mo.add_argument(
        "--report",
        action="store_true",
        help="write the report bundle (with the per-layer section) to "
        "<iteration>/report afterwards",
    )
    mo.add_argument("--label", default=None, help="iteration label")
    mo.add_argument("--note", default="", help="free-form iteration note")
    mo.add_argument(
        "--inject-faults",
        default=None,
        metavar="SPEC",
        help="deterministically inject faults into sharded collection "
        "(e.g. 'seed=7'); recovery is recorded as FaultEvent provenance",
    )
    mo.add_argument(
        "--resume",
        action="store_true",
        help="resume a preempted run from the session's model journal: "
        "kernels the preempted run flushed are reused verbatim, only "
        "the remainder is profiled",
    )
    mo.add_argument(
        "--quiet", "-q", action="store_true",
        help="suppress the per-layer table",
    )
    mo.set_defaults(func=_cmd_model)

    rp = sub.add_parser(
        "report", help="write the report bundle for a stored iteration"
    )
    rp.add_argument(
        "iteration",
        help="iteration directory (sess/iter0), or a session directory "
        "(its latest iteration is used)",
    )
    rp.add_argument(
        "--out",
        "-o",
        default=None,
        metavar="DIR",
        help="bundle output directory (default: <iteration>/report)",
    )
    rp.add_argument("--title", default=None, help="report title")
    rp.set_defaults(func=_cmd_report)

    df = sub.add_parser(
        "diff", help="compare two stored iterations kernel-by-kernel"
    )
    df.add_argument("before", help="baseline iteration directory")
    df.add_argument("after", help="candidate iteration directory")
    df.add_argument(
        "--region-map",
        action="append",
        default=[],
        metavar="KERNEL:OLD=NEW",
        help="rename a region between iterations (repeatable), e.g. "
        "'gramschm:q=qT' when an optimization renames a buffer",
    )
    df.add_argument(
        "--fail-on-regression",
        action="store_true",
        help="exit 1 when any kernel regressed (CI gating)",
    )
    df.set_defaults(func=_cmd_diff)

    ck = sub.add_parser(
        "check",
        help="gate a candidate iteration against a baseline artifact "
        "and/or its own session history (exit 0 pass / 1 fail / 2 error)",
    )
    ck.add_argument(
        "candidate",
        help="candidate iteration directory, or a session directory "
        "(its latest iteration is gated; --anomaly needs a session)",
    )
    ck.add_argument(
        "--baseline",
        "-b",
        default=None,
        metavar="DIR",
        help="baseline iteration (or session) directory to gate against",
    )
    ck.add_argument(
        "--static",
        action="store_true",
        help="no-trace gate: candidate and --baseline are registry refs "
        "(NAME[:VARIANT]) compared on their static lint reports — no "
        "session artifacts are read or written (incompatible with "
        "--anomaly and --region-map)",
    )
    ck.add_argument(
        "--anomaly",
        action="store_true",
        help="also flag kernels whose latest heat map leaves their own "
        "rolling median/MAD history bands (candidate must be a session "
        "directory with enough iterations)",
    )
    ck.add_argument(
        "--threshold",
        "-t",
        action="append",
        default=[],
        metavar="KEY=VALUE",
        help="gate budget (repeatable): transfer-pct, aggregate-pct, "
        "scratch-pct, severity (floats); new-patterns, missing (on|off); "
        "allow-pattern=NAME (exempt a pattern class); defaults are "
        "strict (zero tolerated growth)",
    )
    ck.add_argument(
        "--region-map",
        action="append",
        default=[],
        metavar="KERNEL:OLD=NEW",
        help="rename a region between baseline and candidate "
        "(repeatable), e.g. 'gramschm:q=qT'",
    )
    ck.add_argument(
        "--json",
        default=None,
        metavar="PATH",
        help="write the schema-versioned JSON report to PATH "
        "('-' for stdout; the human summary then moves to stderr)",
    )
    ck.add_argument(
        "--min-history",
        type=int,
        default=None,
        metavar="N",
        help="anomaly bands need N prior iterations (default: 3)",
    )
    ck.add_argument(
        "--nmads",
        type=float,
        default=None,
        metavar="X",
        help="anomaly band half-width in scaled MADs (default: 4.0)",
    )
    ck.add_argument(
        "--include-rejected",
        action="store_true",
        help="band anomaly history over tuner-rejected candidates too",
    )
    ck.add_argument(
        "--quiet", "-q", action="store_true",
        help="suppress the human summary (exit code + JSON only)",
    )
    ck.set_defaults(func=_cmd_check)

    tn = sub.add_parser(
        "tune",
        help="autotune kernels: profile, apply advisor actions, re-profile",
    )
    tn.add_argument(
        "kernel",
        nargs="*",
        metavar="NAME[:VARIANT]",
        help="kernel families to tune (the given variant is the starting "
        "rung; default: the family's baseline)",
    )
    tn.add_argument(
        "--all",
        action="store_true",
        help="concurrent scheduler: tune the listed families (or the "
        "whole registry when none are listed) together on one shared "
        "worker pool under ONE global --budget; deterministic per "
        "--seed via ordered result commitment",
    )
    tn.add_argument(
        "--budget",
        "-b",
        type=int,
        default=None,  # resolved to tuner.DEFAULT_BUDGET in the handler
        metavar="N",
        help="max candidate re-profiles per family, or the global total "
        "across families with --all (default: 8)",
    )
    tn.add_argument(
        "--workers",
        "-w",
        type=int,
        default=1,
        metavar="N",
        help="shard candidate profiling across N worker processes "
        "(registry-buildable candidates only; generated candidates "
        "collect in-process)",
    )
    tn.add_argument(
        "--target-pattern",
        action="append",
        default=[],
        metavar="PATTERN",
        # repro.core.patterns.ALL_PATTERNS, inlined so --help needs no
        # numpy import; a typo must fail loudly, not tune nothing
        choices=(
            "hot", "hot-random", "scratch-abuse", "false-sharing",
            "misalignment", "strided",
        ),
        help="only chase actions for this pattern (repeatable): hot, "
        "hot-random, false-sharing, misalignment, strided, scratch-abuse",
    )
    tn.add_argument(
        "--out",
        "-o",
        default="cuthermo-session",
        metavar="DIR",
        help="session directory the trajectory is persisted into "
        "(default: ./cuthermo-session)",
    )
    tn.add_argument(
        "--cache",
        default=None,
        metavar="DIR",
        help="content-addressed collection cache directory: repeated "
        "candidates return bit-identical stored heat maps instead of "
        "re-tracing (created on first use)",
    )
    tn.add_argument(
        "--seed",
        type=int,
        default=0,
        help="candidate tie-break seed (same seed => same trajectory)",
    )
    tn.add_argument(
        "--no-generated",
        action="store_true",
        help="only try registry ladder variants, no generated candidates",
    )
    tn.add_argument(
        "--no-prescreen",
        action="store_true",
        help="disable the static pre-screen (profile even candidates the "
        "linter prices as strictly worse than the incumbent)",
    )
    tn.add_argument(
        "--inject-faults",
        default=None,
        metavar="SPEC",
        help="deterministically inject faults into sharded collection "
        "(e.g. 'seed=7'); candidate profiles that still fail are "
        "skipped as candidate-failure provenance, never fatal",
    )
    tn.add_argument(
        "--resume",
        action="store_true",
        help="(with --all) resume a preempted run: replay the journaled "
        "arguments deterministically — completed profiles come back "
        "bit-identical from the cache, trajectories are unchanged",
    )
    tn.add_argument(
        "--report",
        action="store_true",
        help="write the report bundle (with the tuning trajectory) to "
        "<out>/report afterwards",
    )
    tn.add_argument(
        "--quiet", "-q", action="store_true",
        help="suppress per-step progress lines",
    )
    tn.set_defaults(func=_cmd_tune)
    return p


# ---------------------------------------------------------------------------
# handlers
# ---------------------------------------------------------------------------


def _parse_sampler(spec: Optional[str]):
    """Parse a ``--sampler`` value into a GridSampler (None = registry's)."""
    if spec is None:
        return None
    from repro.core.trace import GridSampler

    if spec == "full":
        return GridSampler(None)
    if spec.startswith("window:"):
        try:
            window = int(spec.split(":", 1)[1])
        except ValueError:
            window = 0
        if window >= 1:
            return GridSampler((0,), window=window)
    print(
        f"cuthermo: bad --sampler {spec!r} (use 'full' or 'window:N' "
        "with N >= 1)",
        file=sys.stderr,
    )
    raise SystemExit(2)


def _parse_fault_plan(spec: Optional[str]):
    """Parse a ``--inject-faults`` value into a FaultPlan (None = off)."""
    if spec is None:
        return None
    from repro.core.faultinject import FaultInjectError, FaultPlan

    try:
        plan = FaultPlan.parse(spec)
    except FaultInjectError as e:
        print(f"cuthermo: {e}", file=sys.stderr)
        raise SystemExit(2)
    print(f"fault injection armed: {plan.describe()}", file=sys.stderr)
    return plan


def _print_fault_summary(faults) -> None:
    """One stderr line summarizing an iteration's recovery provenance."""
    if not faults:
        return
    from repro.core.resilience import FaultEvent, summarize_faults

    events = tuple(
        FaultEvent.from_dict({k: v for k, v in f.items() if k != "kernel"})
        for f in faults
    )
    print(f"recovered faults: {summarize_faults(events)}", file=sys.stderr)


def _cmd_kernels(args: argparse.Namespace) -> int:
    """Handler for ``cuthermo kernels``."""
    from repro import kernels as kreg

    if args.lint:
        from repro.core.lint import lint_ref

    for name in kreg.names():
        entry = kreg.get(name)
        variants = ", ".join(
            v.name + ("*" if i == 0 else "")
            for i, v in enumerate(entry.variants)
        )
        print(f"{name:<12} [{variants}]  {entry.summary}")
        if args.lint:
            for v in entry.variants:
                rep = lint_ref(f"{name}:{v.name}")
                preds = ", ".join(
                    f"{f.pattern}({f.region})" for f in rep.findings
                )
                tx = (
                    "dynamic"
                    if rep.static_transactions is None
                    else f"{rep.static_transactions} transfers"
                )
                print(
                    f"  {v.name:<10} {rep.verdict():<6} {tx}"
                    + (f"  [{preds}]" if preds else "")
                )
    print("(* = default/baseline variant)")
    if args.lint:
        print("(static lint verdicts: no kernels were run or traced)")
    return 0


def _cmd_lint(args: argparse.Namespace) -> int:
    """Handler for ``cuthermo lint``.

    Exit-code contract (same family as ``check``): 0 clean (or only
    warnings without ``--strict``), 1 findings gate the run (any
    error-level finding; warnings too under ``--strict``), 2 usage
    error (no refs, unknown ref).
    """
    import json as _json

    from repro import kernels as kreg
    from repro.core.lint import LintError, lint_document, lint_ref

    refs = list(args.ref)
    if args.all:
        for name in kreg.names():
            for v in kreg.get(name).variants:
                ref = f"{name}:{v.name}"
                if ref not in refs:
                    refs.append(ref)
    if not refs:
        print(
            "cuthermo lint: nothing to lint "
            "(pass NAME[:VARIANT] refs or --all)",
            file=sys.stderr,
        )
        return 2
    reports = []
    for ref in refs:
        try:
            reports.append(lint_ref(ref))
        except (KeyError, LintError) as e:
            msg = e.args[0] if e.args else e
            print(f"cuthermo: {msg}", file=sys.stderr)
            return 2
    doc = lint_document(reports, strict=args.strict)
    human = "\n\n".join(rep.summary() for rep in reports)
    if not doc["passed"]:
        n = len(doc["failures"])
        human += f"\nlint FAILED ({n} finding{'s' if n != 1 else ''} gate)"
    if args.json == "-":
        print(_json.dumps(doc, indent=2))
        if not args.quiet:
            print(human, file=sys.stderr)
    else:
        if args.json:
            with open(args.json, "w") as fh:
                _json.dump(doc, fh, indent=2)
                fh.write("\n")
        if not args.quiet:
            print(human)
    return 0 if doc["passed"] else 1


def _cmd_profile(args: argparse.Namespace) -> int:
    """Handler for ``cuthermo profile``."""
    from repro import kernels as kreg
    from repro.core.advisor import format_report
    from repro.core.session import (
        ProfileSession,
        SessionError,
        profile_kernel,
    )

    refs = list(args.kernel)
    if args.all:
        refs += [n for n in kreg.names() if n not in refs]
    if not refs:
        print(
            "cuthermo profile: nothing to do "
            "(pass --kernel NAME[:VARIANT] or --all)",
            file=sys.stderr,
        )
        return 2
    override = _parse_sampler(args.sampler)
    plan = _parse_fault_plan(args.inject_faults)
    try:
        resolved = [kreg.resolve(ref) for ref in refs]
    except KeyError as e:
        print(f"cuthermo: {e.args[0]}", file=sys.stderr)
        return 2
    # drop repeated refs ('-k gemm -k gemm', or 'gemm' + 'gemm:v00' which
    # resolve identically), keeping first-occurrence order
    uniq, seen_pairs = [], set()
    for entry, variant in resolved:
        if (entry.name, variant.name) not in seen_pairs:
            seen_pairs.add((entry.name, variant.name))
            uniq.append((entry, variant))
    resolved = uniq
    # kernel names are the iteration's alignment keys; when one invocation
    # profiles several variants of the same kernel, qualify the names
    entry_counts: dict = {}
    for entry, _ in resolved:
        entry_counts[entry.name] = entry_counts.get(entry.name, 0) + 1
    try:
        sess = ProfileSession(args.out, cache=args.cache, fault_plan=plan)
    except SessionError as e:
        print(f"cuthermo: {e}", file=sys.stderr)
        return 2
    workers = max(1, args.workers)
    profiled = []
    try:
        # one warm pool shared by every kernel of this invocation,
        # owned (and closed) by the session
        collector = sess.collector(workers)
        for entry, variant in resolved:
            name = (
                entry.name
                if entry_counts[entry.name] == 1
                else f"{entry.name}:{variant.name}"
            )
            # build through the registry so the spec is source-stamped —
            # that ref is what shard workers rebuild the spec from
            spec, ctx = kreg.build(f"{entry.name}:{variant.name}")
            pk = profile_kernel(
                spec,
                override or entry.sampler(),
                ctx,
                name=name,
                variant=variant.name,
                region_map=entry.region_map,
                collector=collector,
                cache=sess.cache,
            )
            profiled.append(pk)
            if not args.quiet:
                print(f"# {entry.name}:{variant.name}")
                if pk.cached:
                    print("(served from the collection cache)")
                if pk.shards:
                    print(
                        f"(collected in {len(pk.shards)} shards: "
                        + ", ".join(
                            f"#{s.shard} {s.records} records"
                            for s in pk.shards
                        )
                        + ")"
                    )
                print(format_report(pk.heatmap))
                print()
        try:
            it = sess.add_iteration(
                profiled, label=args.label, note=args.note
            )
        except SessionError as e:
            print(f"cuthermo: {e}", file=sys.stderr)
            return 2
    finally:
        sess.close()
    if sess.cache is not None:
        st = sess.cache.stats
        print(
            f"cache: {st.hits} hits ({st.memory_hits} memory, "
            f"{st.disk_hits} disk), {st.misses} misses"
        )
    _print_fault_summary(it.faults)
    print(f"wrote {it.path} ({len(profiled)} kernels)")
    return 0


def _cmd_model(args: argparse.Namespace) -> int:
    """Handler for ``cuthermo model``.

    Exit-code contract: 0 profiled (and under budget), 1 the
    ``--max-transfers`` budget is blown, 2 usage or load error (unknown
    model, bad ``--config`` override, unreadable session, invalid
    ``--resume``), 3 preempted — a SIGTERM/SIGINT flushed a partial
    iteration and left a journal; re-run with ``--resume`` to finish.
    """
    import os

    from repro.core.model_profile import (
        iteration_transactions,
        profile_model,
    )
    from repro.core.session import SessionError

    if args.list:
        from repro.models.registry import MODELS

        for name, entry in MODELS.items():
            cfg = entry.config
            print(
                f"{name:<18} batch={entry.batch} seq={entry.seq} "
                f"layers={cfg.n_layers} d_model={cfg.d_model}  "
                f"{entry.summary}"
            )
        return 0
    if not args.name:
        print(
            "cuthermo model: pass a model NAME (or --list)",
            file=sys.stderr,
        )
        return 2
    import signal

    from repro.runtime.fault import Preempted, PreemptionHandler

    sampler = _parse_sampler(args.sampler)
    plan = _parse_fault_plan(args.inject_faults)
    # SIGTERM/SIGINT flip a flag; profile_model sees it at the next
    # kernel boundary, flushes a partial iteration and raises Preempted
    handler = PreemptionHandler().register(
        (signal.SIGTERM, signal.SIGINT)
    )
    try:
        it = profile_model(
            args.name,
            args.out,
            overrides=args.config,
            backward=args.backward,
            sampler=sampler,
            workers=max(1, args.workers),
            cache=args.cache,
            label=args.label,
            note=args.note,
            hlo=not args.no_hlo,
            fault_plan=plan,
            preemption=handler,
            resume=args.resume,
        )
    except Preempted as e:
        print(f"cuthermo: {e}", file=sys.stderr)
        return 3
    except (KeyError, ValueError, SessionError) as e:
        msg = e.args[0] if e.args else e
        print(f"cuthermo: {msg}", file=sys.stderr)
        return 2
    finally:
        handler.unregister()
    total = iteration_transactions(it)
    layers = it.layers or {}
    if not args.quiet:
        print(f"# model {args.name} (batch {layers.get('batch')}, "
              f"seq {layers.get('seq')})"
              + (" forward+backward" if args.backward else ""))
        for row in layers.get("table", ()):
            pats = ", ".join(
                f"{p}@{r}" for _k, r, p in row.get("patterns", ())
            )
            print(
                f"  {row['path']:<10} {', '.join(row['kinds']):<14} "
                f"{row['transactions']:>8} transfers"
                + (f"  [{pats}]" if pats else "")
            )
        print(f"  {'total':<10} {'':<14} {total:>8} transfers")
        hlo = layers.get("hlo") or {}
        if hlo:
            cost = hlo.get("cost") or {}
            heat = hlo.get("heat") or {}
            print(
                f"  hlo sweep: {cost.get('flops', 0):.3g} flops, "
                f"{cost.get('bytes', 0):.3g} bytes, "
                f"{heat.get('collective_count', 0)} collectives"
            )
    if args.report:
        from repro.core.render import ReportEntry, write_report_bundle

        written = write_report_bundle(
            [ReportEntry.from_profiled(pk) for pk in it.kernels],
            os.path.join(str(it.path), "report"),
            title=f"cuthermo model report — {it.label}",
            layers=layers or None,
            faults=list(it.faults) or None,
        )
        print(f"wrote {written['index.html']}")
    _print_fault_summary(it.faults)
    print(f"wrote {it.path} ({len(it.kernels)} kernels, {total} transfers)")
    if args.max_transfers is not None and total > args.max_transfers:
        print(
            f"cuthermo: transfer budget blown: {total} > "
            f"{args.max_transfers}",
            file=sys.stderr,
        )
        return 1
    return 0


def _resolve_iteration_dir(path: str):
    """Accept an iteration dir, or a session dir (use its last iteration)."""
    import os

    from repro.core.session import ProfileSession, SessionError, load_iteration

    if os.path.isfile(os.path.join(path, "session.json")):
        sess = ProfileSession(path, create=False)
        names = sess.iteration_names()
        if not names:
            raise SessionError(f"{path}: session has no iterations yet")
        return sess.iteration(-1)
    return load_iteration(path)


def _cmd_report(args: argparse.Namespace) -> int:
    """Handler for ``cuthermo report``."""
    import dataclasses
    import os

    from repro.core.render import ReportEntry, write_report_bundle
    from repro.core.session import ProfileSession, SessionError

    try:
        it = _resolve_iteration_dir(args.iteration)
    except SessionError as e:
        print(f"cuthermo: {e}", file=sys.stderr)
        return 2
    # pointed at a session root: recover any stored tuning trajectories
    # (v3 provenance) so the bundle gets its trajectory section, and
    # render each tuning run's WINNING iteration as the report body
    # (the latest iteration may well be a rejected candidate)
    tuning = None
    kernels = list(it.kernels)
    if os.path.isfile(os.path.join(args.iteration, "session.json")):
        from repro.core.session import load_iteration
        from repro.core.tuner import trajectories_from_session

        sess = ProfileSession(args.iteration, create=False)
        tuning = trajectories_from_session(sess) or None
        # swap the report body to each run's winner ONLY when the
        # resolved latest iteration is itself part of a tuning run —
        # plain profiles appended after a tune must stay the body
        if tuning and it.tuning is not None:
            best = []
            for traj in tuning:
                name = traj["best"].get("iteration")
                try:
                    best.extend(load_iteration(sess.root / name).kernels)
                except (SessionError, TypeError):
                    best = []  # incomplete provenance: keep the default
                    break
            if best:
                kernels = best
                it = dataclasses.replace(it, label=f"{it.label} (tuned)")
    entries = [ReportEntry.from_profiled(pk) for pk in kernels]
    out = args.out or os.path.join(str(it.path), "report")
    title = args.title or f"cuthermo report — {it.label}"
    # fold in the latest `cuthermo check` verdict when one was stored
    # next to the iteration (tolerate a corrupt/foreign file: the check
    # section is additive, never a reason to fail the bundle)
    check = None
    check_path = it.path / "check.json"
    if check_path.is_file():
        import json as _json

        try:
            doc = _json.loads(check_path.read_text())
            if isinstance(doc, dict) and doc.get("format") == "cuthermo-check":
                check = doc
        except (OSError, ValueError):
            check = None
    # predicted-vs-observed lint cross-tab: re-lint each kernel's
    # registry ref (specs are cheap to rebuild; no traces) and line the
    # static predictions up against the stored dynamic detections.
    # Best-effort: tuner-generated variants (pin(A), retile 2x...) have
    # no registry ref and are simply skipped.
    lint = []
    from repro.core.lint import LintError, lint_ref, predicted_vs_observed

    for pk in kernels:
        family = pk.name.partition(":")[0]
        ref = f"{family}:{pk.variant}"
        try:
            rep = lint_ref(ref)
        except (KeyError, LintError):
            continue
        lint.append(
            {
                "kernel": pk.name,
                "ref": ref,
                "verdict": rep.verdict(),
                "static_transactions": rep.static_transactions,
                "rows": predicted_vs_observed(rep, pk.reports),
            }
        )
    written = write_report_bundle(
        entries, out, title=title, tuning=tuning, check=check,
        lint=lint or None, layers=it.layers,
        faults=list(it.faults) or None,
    )
    print(f"wrote {written['index.html']}")
    print(f"wrote {written['report.md']}")
    return 0


def _cmd_tune(args: argparse.Namespace) -> int:
    """Handler for ``cuthermo tune``.

    Exit-code contract: 0 tuned, 2 usage or load error, 3 preempted —
    with ``--all``, a SIGTERM/SIGINT stopped the scheduler at a round
    boundary (committed iterations are durable, the run journal stays);
    ``cuthermo tune --all --resume`` replays the journaled run
    deterministically, so the finished trajectories are identical to an
    uninterrupted run's.
    """
    import json as _json
    import os

    from repro.core.session import ProfileSession, SessionError
    from repro.core.tuner import DEFAULT_BUDGET, TuneError

    if not args.kernel and not args.all:
        print(
            "cuthermo tune: nothing to do "
            "(pass NAME[:VARIANT] families or --all)",
            file=sys.stderr,
        )
        return 2
    if args.resume and not args.all:
        print(
            "cuthermo tune: --resume requires --all (single-family tune "
            "has no run journal)",
            file=sys.stderr,
        )
        return 2
    plan = _parse_fault_plan(args.inject_faults)
    try:
        sess = ProfileSession(args.out, cache=args.cache, fault_plan=plan)
    except SessionError as e:
        print(f"cuthermo: {e}", file=sys.stderr)
        return 2
    progress = None if args.quiet else (lambda msg: print(f"  {msg}"))
    budget = DEFAULT_BUDGET if args.budget is None else max(0, args.budget)
    workers = max(1, args.workers)
    results = []
    try:
        if args.all:
            import signal

            from repro.core.tuner import tune_all
            from repro.runtime.fault import Preempted, PreemptionHandler

            run = {
                "format": "cuthermo-tune-journal",
                "version": 1,
                "kernels": list(args.kernel),
                "budget": budget,
                "seed": args.seed,
                "target_patterns": list(args.target_pattern),
                "use_generated": not args.no_generated,
                "static_prescreen": not args.no_prescreen,
            }
            jpath = sess.root / "tune.journal.json"
            if args.resume:
                # resume-by-replay: the journal's arguments, not the
                # command line's, define the run — re-executing them is
                # deterministic (seeded tie-breaks, ordered commitment)
                # and cheap (completed profiles hit the cache)
                try:
                    run = _json.loads(jpath.read_text())
                except (OSError, _json.JSONDecodeError) as e:
                    print(
                        f"cuthermo: nothing to resume ({jpath}: {e})",
                        file=sys.stderr,
                    )
                    return 2
                if run.get("format") != "cuthermo-tune-journal":
                    print(
                        f"cuthermo: {jpath} is not a tune journal",
                        file=sys.stderr,
                    )
                    return 2
                print(
                    f"resuming journaled tune --all (seed {run['seed']}, "
                    f"budget {run['budget']})",
                    file=sys.stderr,
                )
            else:
                tmp = jpath.with_name(jpath.name + ".tmp")
                tmp.write_text(_json.dumps(run, indent=2) + "\n")
                os.replace(tmp, jpath)
            handler = PreemptionHandler().register(
                (signal.SIGTERM, signal.SIGINT)
            )
            try:
                res_all = tune_all(
                    run["kernels"] or None,
                    budget=int(run["budget"]),
                    target_patterns=run["target_patterns"] or None,
                    seed=int(run["seed"]),
                    use_generated=bool(run["use_generated"]),
                    static_prescreen=bool(run["static_prescreen"]),
                    session=sess,
                    collector=sess.collector(workers),
                    cache=sess.cache,
                    progress=progress,
                    preemption=handler,
                )
            except Preempted as e:
                print(f"cuthermo: {e}", file=sys.stderr)
                print(
                    "cuthermo: run journal kept; finish with "
                    "`cuthermo tune --all --resume`",
                    file=sys.stderr,
                )
                return 3
            except (TuneError, SessionError) as e:
                print(f"cuthermo: {e}", file=sys.stderr)
                return 2
            finally:
                handler.unregister()
            jpath.unlink(missing_ok=True)
            results = list(res_all.results)
            print(res_all.summary())
            print()
        else:
            for ref in args.kernel:
                if not args.quiet:
                    print(f"# tuning {ref}")
                try:
                    res = sess.tune(
                        ref,
                        budget=budget,
                        target_patterns=args.target_pattern or None,
                        seed=args.seed,
                        use_generated=not args.no_generated,
                        static_prescreen=not args.no_prescreen,
                        workers=workers,
                        progress=progress,
                    )
                except (TuneError, SessionError) as e:
                    print(f"cuthermo: {e}", file=sys.stderr)
                    return 2
                results.append(res)
                print(res.summary())
                print()
    finally:
        sess.close()
    if sess.cache is not None:
        st = sess.cache.stats
        print(
            f"cache: {st.hits} hits ({st.memory_hits} memory, "
            f"{st.disk_hits} disk), {st.misses} misses"
        )
    if args.report:
        from repro.core.render import ReportEntry, write_report_bundle

        written = write_report_bundle(
            [ReportEntry.from_profiled(r.best) for r in results],
            os.path.join(args.out, "report"),
            title="cuthermo tune report",
            tuning=[r.as_dict() for r in results],
            faults=[
                dict(e.as_dict(), kernel=r.kernel)
                for r in results
                for e in r.faults
            ] or None,
        )
        print(f"wrote {written['index.html']}")
    improved = sum(1 for r in results if r.improved)
    fixed = sum(len(r.fixed_patterns) for r in results)
    print(
        f"tuned {len(results)} kernel(s): {improved} improved, "
        f"{fixed} patterns fixed (trajectory in {sess.root})"
    )
    return 0


def _parse_region_maps(specs):
    """Parse repeated ``--region-map KERNEL:OLD=NEW`` flags.

    Returns the nested mapping, or None (after printing to stderr) on a
    malformed spec — callers turn that into exit code 2.
    """
    region_maps: dict = {}
    for spec in specs:
        try:
            kernel, rename = spec.split(":", 1)
            old, new = rename.split("=", 1)
        except ValueError:
            print(
                f"cuthermo: bad --region-map {spec!r} "
                "(expected KERNEL:OLD=NEW)",
                file=sys.stderr,
            )
            return None
        region_maps.setdefault(kernel, {})[old] = new
    return region_maps


def _cmd_diff(args: argparse.Namespace) -> int:
    """Handler for ``cuthermo diff``.

    Exit-code contract (same as ``check``): 0 no regression, 1 gate
    failure under ``--fail-on-regression``, 2 usage or load error.
    """
    from repro.core.session import SessionError, diff_iterations, load_iteration

    region_maps = _parse_region_maps(args.region_map)
    if region_maps is None:
        return 2
    try:
        before = load_iteration(args.before)
        after = load_iteration(args.after)
    except SessionError as e:
        print(f"cuthermo: {e}", file=sys.stderr)
        return 2
    sd = diff_iterations(before, after, region_maps=region_maps)
    print(sd.summary())
    if args.fail_on_regression and sd.regressed:
        return 1
    return 0


def _cmd_check(args: argparse.Namespace) -> int:
    """Handler for ``cuthermo check``.

    Exit-code contract: 0 every gate held, 1 at least one gate failed
    (threshold blown, new/worsened pattern, missing kernel, anomaly
    flag), 2 usage or load error (bad flags, unreadable artifacts).
    """
    import json as _json
    import os

    from repro.core.check import (
        CheckError,
        CheckThresholds,
        check_iterations,
        check_session_anomalies,
        check_static,
        merge_reports,
    )
    from repro.core.session import ProfileSession, SessionError

    if not args.baseline and not args.anomaly:
        print(
            "cuthermo check: nothing to gate against "
            "(pass --baseline DIR and/or --anomaly)",
            file=sys.stderr,
        )
        return 2
    region_maps = _parse_region_maps(args.region_map)
    if region_maps is None:
        return 2
    try:
        thresholds = CheckThresholds.from_specs(args.threshold)
    except CheckError as e:
        print(f"cuthermo: {e}", file=sys.stderr)
        return 2

    if args.static:
        if args.anomaly or args.region_map:
            print(
                "cuthermo check: --static takes registry refs and is "
                "incompatible with --anomaly / --region-map (the family's "
                "registry region_map applies automatically)",
                file=sys.stderr,
            )
            return 2
        if not args.baseline:
            print(
                "cuthermo check: --static needs --baseline NAME[:VARIANT]",
                file=sys.stderr,
            )
            return 2
        try:
            report = check_static(
                args.candidate, args.baseline, thresholds=thresholds
            )
        except CheckError as e:
            print(f"cuthermo: {e}", file=sys.stderr)
            return 2
        doc = report.as_dict()
        if args.json == "-":
            print(_json.dumps(doc, indent=2))
            if not args.quiet:
                print(report.summary(), file=sys.stderr)
        else:
            if args.json:
                with open(args.json, "w") as fh:
                    _json.dump(doc, fh, indent=2)
                    fh.write("\n")
            if not args.quiet:
                print(report.summary())
        return 0 if report.passed else 1

    report = None
    candidate_it = None
    try:
        if args.baseline:
            baseline = _resolve_iteration_dir(args.baseline)
            candidate_it = _resolve_iteration_dir(args.candidate)
            report = check_iterations(
                baseline,
                candidate_it,
                thresholds=thresholds,
                region_maps=region_maps,
            )
        if args.anomaly:
            if not os.path.isfile(
                os.path.join(args.candidate, "session.json")
            ):
                print(
                    f"cuthermo: --anomaly needs a session directory, and "
                    f"{args.candidate!r} has no session.json",
                    file=sys.stderr,
                )
                return 2
            sess = ProfileSession(args.candidate, create=False)
            kwargs = {"include_rejected": args.include_rejected}
            if args.min_history is not None:
                kwargs["min_history"] = args.min_history
            if args.nmads is not None:
                kwargs["nmads"] = args.nmads
            anomaly_report = check_session_anomalies(sess, **kwargs)
            report = (
                merge_reports(report, anomaly_report)
                if report is not None
                else anomaly_report
            )
    except (CheckError, SessionError) as e:
        print(f"cuthermo: {e}", file=sys.stderr)
        return 2

    doc = report.as_dict()
    # drop a copy next to the candidate artifact so `cuthermo report`
    # can fold the verdict into the bundle; best-effort (a read-only
    # artifact tree must not turn a clean gate into an error)
    if candidate_it is not None:
        try:
            (candidate_it.path / "check.json").write_text(
                _json.dumps(doc, indent=2) + "\n"
            )
        except OSError:
            pass
    if args.json == "-":
        print(_json.dumps(doc, indent=2))
        if not args.quiet:
            print(report.summary(), file=sys.stderr)
    else:
        if args.json:
            with open(args.json, "w") as fh:
                _json.dump(doc, fh, indent=2)
                fh.write("\n")
        if not args.quiet:
            print(report.summary())
    return 0 if report.passed else 1


def main(argv: Optional[List[str]] = None) -> int:
    """Entry point for the ``cuthermo`` console script."""
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    parser = _build_parser()
    args = parser.parse_args(argv)
    if not getattr(args, "func", None):
        parser.print_help()
        return 2
    return args.func(args)


if __name__ == "__main__":
    sys.exit(main())
