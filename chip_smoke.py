#!/usr/bin/env python3
"""Bring-up smoke run on one TPU chip: kernels, profiler, a full-width server.

    python chip_smoke.py [--seed N]

Everything runs in this one process (a chip belongs to one process), in
order, and any failure raises, so the exit code is non-zero:

1. device check: the platform must be ``tpu``; there is no CPU fallback;
2. kernels: every ``pallas_call`` at the registry's default shapes with
   ``interpret=False``, against its oracle at ``precision=HIGHEST``;
3. profiler: ``repro.cli.main`` in-process: ``profile -k gemm`` serially
   and with ``--workers 2`` (their ``diff`` must be ``unchanged``),
   ``tune gemm --budget 2`` and ``model transformer-tiny``;
4. model: granite-3-2b at its published widths and depth, random weights
   from the seed, behind ``repro.runtime.Server``: 4 requests of 16 new
   tokens on 4 slots, one checked against ``model.apply``.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Times printed on the way are sanity lines, not benchmark metrics.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import pathlib
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# largest |out - oracle| over largest |oracle|, by output dtype; outputs
# that count (histograms) must match exactly.  The kernels multiply f32
# at HIGHEST; Mosaic's default, one bf16 pass, gives 2e-3 to 6e-3 on a
# v5e, so the f32 limit tells the two apart.
TOL = {"float32": 1e-4, "bfloat16": 5e-2}

SERVE_ARCH = "granite-3-2b"
PROMPT_LEN = 112  # every prompt the same length: one prefill compile
NEW_TOKENS = 16
REQUESTS = 4
SLOTS = 4


def log(msg: str) -> None:
    print(msg, flush=True)


def check_device():
    """Phase 1: the default backend must be a TPU."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"no TPU: JAX's default platform is {dev.platform!r}; "
            "this smoke run does not fall back to the CPU"
        )
    log(f"[device] {dev.platform} {dev.device_kind!r} x {len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}


def rel_err(got, want) -> float:
    """Largest absolute error over the oracle's largest magnitude."""
    import numpy as np

    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def run_kernels(seed: int, interpret: bool = False) -> None:
    """Phase 2: every kernel case against its oracle."""
    import jax
    import numpy as np

    from repro.kernels.cases import CASES

    for case in CASES:
        args = case.inputs(np.random.default_rng(seed))
        fn = jax.jit(lambda *a, run=case.run: run(*a, interpret=interpret))
        jax.block_until_ready(fn(*args))  # warm-up: compile
        t0 = time.perf_counter()
        got = jax.block_until_ready(fn(*args))
        wall = time.perf_counter() - t0
        with jax.default_matmul_precision("highest"):
            want = case.oracle(*args)
        got_leaves = jax.tree.leaves(got)
        want_leaves = jax.tree.leaves(want)
        if len(got_leaves) != len(want_leaves):
            raise AssertionError(f"{case.name}: {len(got_leaves)} outputs, oracle has {len(want_leaves)}")
        err = max(rel_err(g, w) for g, w in zip(got_leaves, want_leaves))
        dtype = str(got_leaves[0].dtype)
        tol = 0.0 if case.exact else TOL[dtype]
        log(f"[kernel] {case.name:15s} {dtype} max_rel_err={err:.3e} "
            f"(tol {tol:g}) wall={wall * 1e3:.3f}ms")
        if not err <= tol:
            raise AssertionError(f"{case.name}: error {err:.3e} above {tol:g}")


def cli(*argv: str) -> str:
    """Run ``cuthermo`` in-process; its stdout and stderr, echoed; raises
    unless exit 0."""
    from repro.cli import main as cuthermo

    out, err = io.StringIO(), io.StringIO()
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = cuthermo(list(argv))
    for line in out.getvalue().rstrip().splitlines():
        log(f"    {line}")
    for line in err.getvalue().rstrip().splitlines():
        log(f"    stderr: {line}")
    log(f"[cli] cuthermo {' '.join(argv)} -> exit {rc} in {time.perf_counter() - t0:.2f}s")
    if rc != 0:
        raise RuntimeError(f"cuthermo {' '.join(argv)} exited {rc}")
    return out.getvalue()


def profile_serial_and_pooled(sess: str, *pool_args: str) -> None:
    """``profile -k gemm`` serially, then on a 2-worker pool; the pooled
    run must collect in 2 shards with no fault recovered, and match."""
    from repro.core.session import load_iteration

    cli("profile", "-k", "gemm", "--out", sess, "-q")
    cli("profile", "-k", "gemm", "--out", sess, "--workers", "2", "-q", *pool_args)
    pooled = load_iteration(f"{sess}/iter1")
    if pooled.faults:
        kinds = sorted({f["kind"] for f in pooled.faults})
        raise AssertionError(f"the --workers 2 pool recovered from faults: {kinds}")
    shards = [len(pk.shards) for pk in pooled.kernels]
    if shards != [2]:
        raise AssertionError(f"the --workers 2 profile ran in {shards} shards, not [2]")
    out = cli("diff", f"{sess}/iter0", f"{sess}/iter1")
    if "unchanged" not in out:
        raise AssertionError("serial and --workers 2 profiles differ")


def run_profiler(workdir: pathlib.Path) -> None:
    """Phase 3: the profiler's entry points, in this process."""
    profile_serial_and_pooled(str(workdir / "profile"))
    cli("tune", "gemm", "--budget", "2", "--out", str(workdir / "tune"), "-q")
    cli("model", "transformer-tiny", "--out", str(workdir / "model"), "-q")


def serve(cfg, seed: int, prompt_len: int = PROMPT_LEN) -> None:
    """Phase 4: answer REQUESTS requests through Server, check one."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import build_model
    from repro.runtime import Request, ServeConfig, Server

    model = build_model(cfg)
    total, _ = cfg.param_counts()
    t0 = time.perf_counter()
    params = jax.block_until_ready(jax.jit(model.init)(jax.random.key(seed)))
    log(f"[model] {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads} heads / {cfg.n_kv_heads} kv, d_ff {cfg.d_ff}, "
        f"{total / 1e9:.3f}B params {jnp.dtype(cfg.dtype).name}, "
        f"init {time.perf_counter() - t0:.2f}s")
    srv = Server(
        model, params,
        ServeConfig(batch_slots=SLOTS, max_seq=prompt_len + NEW_TOKENS, seed=seed),
        dtype=cfg.dtype,
    )
    rng = np.random.default_rng(seed)
    reqs = [
        Request(rid=i, prompt=rng.integers(0, cfg.vocab, prompt_len).astype(np.int32),
                max_tokens=NEW_TOKENS)
        for i in range(REQUESTS)
    ]
    for r in reqs:
        srv.submit(r)
    t0 = time.perf_counter()
    srv.run_until_done()
    wall = time.perf_counter() - t0
    if not all(r.done and len(r.out_tokens) == NEW_TOKENS for r in reqs):
        raise AssertionError("not every request was answered in full")
    log(f"[serve] {REQUESTS} requests x {NEW_TOKENS} new tokens on {SLOTS} slots, "
        f"prompts of {prompt_len}: {srv.counters['decode_ticks']} decode ticks, "
        f"{wall:.2f}s including compiles, compiles {srv.compiles}")
    if srv.compiles != {"prefill": 1, "decode": 1}:
        raise AssertionError(f"expected one prefill and one decode compile: {srv.compiles}")

    # the final decode step of request 0 (slot 0) against a plain forward
    # pass over its whole sequence, at the same position
    r = reqs[0]
    seq = np.concatenate([r.prompt, np.asarray(r.out_tokens[:-1], np.int32)])
    ref = jax.jit(lambda p, t: model.apply(p, t)[0][0, -1])(params, seq[None])
    err = rel_err(srv.last_logits[0, 0], ref)
    tol = TOL[jnp.dtype(cfg.dtype).name]
    log(f"[serve] reference check, request 0 final step (position {len(seq) - 1}): "
        f"max_rel_err={err:.3e} (tol {tol:g})")
    if not err <= tol:
        raise AssertionError(f"served logits differ from model.apply: {err:.3e}")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"[memory] peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
        f"bytes_limit={stats.get('bytes_limit')}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    cache_events = {"requests": 0, "hits": 0}

    def on_event(event: str, **_) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            cache_events["requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1

    jax.monitoring.register_event_listener(on_event)

    device = check_device()
    run_kernels(args.seed)
    with tempfile.TemporaryDirectory() as tmp:
        run_profiler(pathlib.Path(tmp))
    from repro.configs import get_config

    serve(get_config(SERVE_ARCH), args.seed)
    log(f"[cache] {cache_dir}: {cache_events['hits']} of "
        f"{cache_events['requests']} cacheable compiles read back")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
