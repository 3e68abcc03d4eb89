"""``Server``'s host spans and counters, and the layer scopes of the steps.

A small server runs under ``jax.profiler``; its ``.xplane.pb`` is read
back with ``ProfileData``.  The spans nest as the server's docstring
says, carry their arguments, and add up to the counters' deltas.
"""

import gc
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.models import build_model
from repro.runtime import Request, ServeConfig, Server, spans

PARENT = {"serve.step": None, "serve.admit": "serve.step", "serve.decode": "serve.step",
          "serve.prefill": "serve.admit", "serve.splice": "serve.admit"}


def traced(log_dir: pathlib.Path, fn):
    """Run ``fn`` under the profiler; the host spans it recorded, by start:
    (name, start_ns, end_ns, args)."""
    jax.profiler.start_trace(str(log_dir))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(log_dir.glob("plugins/profile/*/*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.end_ns, dict(e.stats)) for e in line.events
                    if e.name.startswith("serve.") or e.name == "gc"]
    return sorted(out, key=lambda s: (s[1], -s[2]))


def parent(span, all_spans):
    """The innermost other ``serve.*`` span that holds ``span``."""
    holders = [s for s in all_spans if s is not span and s[0] != "gc"
               and s[1] <= span[1] and span[2] <= s[2]]
    return min(holders, key=lambda s: s[2] - s[1])[0] if holders else None


@pytest.fixture(scope="module")
def small_server():
    cfg = get_config("granite-3-2b", smoke=True)
    model = build_model(cfg)
    srv = Server(model, model.init(jax.random.key(0)), ServeConfig(batch_slots=3, max_seq=32),
                 dtype=jnp.float32)
    warm = [Request(rid=100 + n, prompt=np.arange(n, dtype=np.int32) + 1, max_tokens=2)
            for n in (4, 6)]
    for r in warm:  # compile both prompt lengths before the traced run
        srv.submit(r)
    srv.run_until_done()
    return srv


def test_spans_nest_carry_args_and_sum_to_the_counters(small_server, tmp_path):
    srv = small_server
    reqs = [Request(rid=i, prompt=np.arange(4 + 2 * (i % 2), dtype=np.int32) + i, max_tokens=3)
            for i in range(5)]
    before = dict(srv.counters)

    def serve():
        srv.caches = srv.model.init_caches(3, 32, dtype=jnp.float32)
        for r in reqs:
            srv.submit(r)
        srv.run_until_done()

    got = traced(tmp_path, serve)
    delta = {k: srv.counters[k] - before[k] for k in before}
    by = {}
    for s in got:
        by.setdefault(s[0], []).append(s)

    for s in got:
        if s[0] in PARENT:
            assert parent(s, got) == PARENT[s[0]], s
        elif s[0] == "serve.sample":  # one per admitted request, one per tick
            assert parent(s, got) == ("serve.admit" if "rid" in s[3] else "serve.step"), s

    prefills = by["serve.prefill"]
    assert [p[3]["rid"] for p in prefills] == [r.rid for r in reqs]
    assert all(p[3]["rows"] == 3 and p[3]["used"] == 1 for p in prefills)
    assert [p[3]["plen"] for p in prefills] == [len(r.prompt) for r in reqs]
    splices = by["serve.splice"]
    assert [(s[3]["rid"], s[3]["slot"]) for s in splices] == \
        [(p[3]["rid"], p[3]["slot"]) for p in prefills]
    assert sum(a[3]["admitted"] for a in by["serve.admit"]) == len(reqs)

    assert delta["prefill_calls"] == len(prefills) == len(reqs)
    assert delta["prefill_calls"] == sum(p[3]["used"] for p in prefills)
    assert delta["prefill_tokens_used"] == sum(p[3]["plen"] for p in prefills) == \
        sum(len(r.prompt) for r in reqs)

    decodes = by["serve.decode"]
    ticks = [s for s in by["serve.sample"] if "tick" in s[3]]
    assert delta["decode_ticks"] == len(decodes) == len(ticks)
    assert [d[3]["tick"] for d in decodes] == [t[3]["tick"] for t in ticks] == \
        list(range(before["decode_ticks"], before["decode_ticks"] + len(decodes)))
    assert all(d[3]["rows"] == 3 for d in decodes)
    assert delta["decode_rows_used"] == sum(d[3]["used"] for d in decodes)
    assert [d[3]["used"] for d in decodes] == [t[3]["slots"] for t in ticks]
    # every token served was sampled in one slot of a sample span
    assert sum(s[3]["slots"] for s in by["serve.sample"]) == sum(len(r.out_tokens) for r in reqs)
    assert [s[3]["tick"] for s in by["serve.step"]] == [d[3]["tick"] for d in decodes]
    # one read of the step's picks per prefill call and per decode tick
    assert delta["sample_reads"] == delta["prefill_calls"] + delta["decode_ticks"]


def test_slowest_step_is_kept_until_reset(small_server):
    srv = small_server
    srv.counters["step_max_s"] = 0.0
    srv.caches = srv.model.init_caches(3, 32, dtype=jnp.float32)
    srv.submit(Request(rid=7, prompt=np.arange(4, dtype=np.int32) + 1, max_tokens=2))
    first = srv.counters["decode_ticks"]
    srv.run_until_done()
    assert srv.counters["step_max_s"] > 0.0
    assert first <= srv.counters["step_max_tick"] < srv.counters["decode_ticks"]


def test_gc_spans_and_counts(tmp_path):
    spans.watch_gc()
    spans.watch_gc()
    assert gc.callbacks.count(spans._on_gc) == 1
    before = spans.gc_counters()
    got = traced(tmp_path, gc.collect)
    after = spans.gc_counters()
    assert after["gc_collections.2"] == before["gc_collections.2"] + 1
    assert after["gc_s.2"] > before["gc_s.2"]
    assert [s[3] for s in got if s[0] == "gc"][-1] == {"gen": 2}


SCOPES = {  # arch: scopes its steps must carry
    "granite-3-2b": ("embed", "stack", "attn", "mlp", "unembed"),
    "mamba2-2.7b": ("embed", "stack", "ssm", "ssm/scan", "unembed"),
    "deepseek-v3-671b": ("mla", "moe"),
}


@pytest.mark.parametrize("step", ["decode_step", "prefill"])
@pytest.mark.parametrize("arch", sorted(SCOPES))
def test_steps_carry_layer_scopes(arch, step):
    model = build_model(get_config(arch, smoke=True))
    params = model.abstract_params()
    caches = model.init_caches(2, 16, dtype=jnp.float32, abstract=True)
    tokens = jax.ShapeDtypeStruct((2, 1 if step == "decode_step" else 8), jnp.int32)
    hlo = jax.jit(getattr(model, step)).lower(params, tokens, caches).compile().as_text()
    paths = re.findall(r'op_name="([^"]+)"', hlo)
    for scope in SCOPES[arch]:
        assert any(f"/{scope}/" in p for p in paths), (scope, paths[:5])
