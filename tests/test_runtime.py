"""Runtime: train loop, grad-accum equivalence, compression, fault, serve."""

import os
import signal
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.data import DataConfig, SyntheticSource, TokenPipeline
from repro.models import ModelConfig, build_model
from repro.optim import adamw, constant, cosine_warmup
from repro.parallel.compression import CompressionConfig, compress, decompress, init_error_buffer
from repro.runtime import (
    Preempted,
    PreemptionHandler,
    Request,
    ServeConfig,
    Server,
    StragglerMonitor,
    TrainConfig,
    build_train_step,
    init_state,
    retry,
    run,
)
from repro.runtime import serve as serve_mod


def _tiny():
    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab=128,
                      dtype=jnp.float32)
    return cfg, build_model(cfg)


def test_training_reduces_loss():
    cfg, m = _tiny()
    opt = adamw(cosine_warmup(5e-3, 5, 60))
    tc = TrainConfig()
    state = init_state(m.init(jax.random.key(0)), opt, tc)
    step = build_train_step(lambda p, t, l: m.loss(p, t, l), opt, tc)
    dc = DataConfig(global_batch=8, seq_len=24, vocab=cfg.vocab)
    pipe = TokenPipeline(SyntheticSource(dc))
    first = None
    for i, (t, l) in zip(range(40), pipe):
        state, metrics = step(state, jnp.asarray(t), jnp.asarray(l))
        if first is None:
            first = float(metrics["loss"])
    assert float(metrics["loss"]) < first - 0.5


def test_grad_accum_equivalence():
    """accum=2 over batch 8 == accum=1 over the same batch (same grads)."""
    cfg, m = _tiny()
    opt = adamw(constant(1e-2))
    params = m.init(jax.random.key(0))
    dc = DataConfig(global_batch=8, seq_len=16, vocab=cfg.vocab)
    tokens, labels = next(TokenPipeline(SyntheticSource(dc)))
    t, l = jnp.asarray(tokens), jnp.asarray(labels)

    s1 = build_train_step(lambda p, a, b: m.loss(p, a, b), opt,
                          TrainConfig(grad_accum=1), donate=False)
    s2 = build_train_step(lambda p, a, b: m.loss(p, a, b), opt,
                          TrainConfig(grad_accum=2), donate=False)
    st1, _ = s1(init_state(params, opt, TrainConfig()), t, l)
    st2, _ = s2(init_state(params, opt, TrainConfig(grad_accum=2)), t, l)
    for a, b in zip(jax.tree.leaves(st1.params), jax.tree.leaves(st2.params)):
        np.testing.assert_allclose(a, b, atol=2e-6, rtol=2e-5)


@pytest.mark.parametrize("mode", ["bf16", "int8"])
def test_compression_roundtrip_and_error_feedback(mode):
    cfg = CompressionConfig(mode=mode)
    g = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(64, 64)) * 1e-3,
                          jnp.float32)}
    err = init_error_buffer(g, cfg)
    wire, err2 = compress(g, err, cfg)
    deq = decompress(wire, cfg)
    # quantization error is bounded and captured by the error buffer
    resid = float(jnp.abs(deq["w"] + err2["w"] - g["w"]).max())
    assert resid < 1e-6
    if mode == "int8":
        assert wire["w"][0].dtype == jnp.int8


def test_compressed_training_converges():
    cfg, m = _tiny()
    opt = adamw(constant(5e-3))
    tc = TrainConfig(compression=CompressionConfig(mode="int8"))
    state = init_state(m.init(jax.random.key(0)), opt, tc)
    step = build_train_step(lambda p, t, l: m.loss(p, t, l), opt, tc)
    dc = DataConfig(global_batch=8, seq_len=16, vocab=cfg.vocab)
    pipe = TokenPipeline(SyntheticSource(dc))
    losses = []
    for i, (t, l) in zip(range(30), pipe):
        state, metrics = step(state, jnp.asarray(t), jnp.asarray(l))
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] - 0.3


def test_straggler_monitor_flags_outlier():
    mon = StragglerMonitor(threshold=6.0, warmup=5)
    for i in range(30):
        mon.observe(i, 0.1 + 0.001 * (i % 3) if i != 20 else 0.5)
    assert any(e.step == 20 for e in mon.events)
    # the 5x outlier dominates every natural-jitter event by z-score
    assert max(mon.events, key=lambda e: e.zscore).step == 20


def test_preemption_checkpoint_and_restart(tmp_path):
    cfg, m = _tiny()
    opt = adamw(constant(1e-3))
    tc = TrainConfig()
    state = init_state(m.init(jax.random.key(0)), opt, tc)
    step = build_train_step(lambda p, t, l: m.loss(p, t, l), opt, tc, donate=False)
    dc = DataConfig(global_batch=4, seq_len=16, vocab=cfg.vocab)
    pipe = TokenPipeline(SyntheticSource(dc))
    mgr = CheckpointManager(str(tmp_path))
    handler = PreemptionHandler().register(signals=(signal.SIGUSR1,))
    captured = {}

    def state_fn():
        return {"params": captured["state"].params}, {"data_step": pipe.state()}

    def capture_hook(i, st, metrics):
        captured["state"] = st
        if i == 3:
            os.kill(os.getpid(), signal.SIGUSR1)  # simulated preemption

    hooks = (capture_hook, handler.checkpoint_hook(mgr, state_fn))
    with pytest.raises(Preempted):
        run(step, state, pipe, 10, hooks)
    handler.unregister()
    # the emergency checkpoint is restorable and data position is saved
    assert mgr.latest_step() is not None
    target = {"params": jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state.params)}
    restored, ck, extra = mgr.restore(target)
    assert extra["data_step"] >= 4


def test_retry_backoff():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise IOError("transient")
        return "ok"

    assert retry(flaky, attempts=4, base_delay=0.001)() == "ok"
    assert calls["n"] == 3


def test_server_matches_direct_decode():
    cfg, m = _tiny()
    params = m.init(jax.random.key(0))
    prompt = np.array([3, 7, 11], np.int32)
    # direct greedy
    caches = m.init_caches(1, 32, dtype=jnp.float32)
    lg, caches = m.prefill(params, jnp.asarray(prompt)[None], caches)
    toks = [int(jnp.argmax(lg[0, -1]))]
    for _ in range(4):
        lg, caches = m.decode_step(params, jnp.asarray([[toks[-1]]]), caches)
        toks.append(int(jnp.argmax(lg[0, 0])))
    # server with 2 slots and an interfering second request
    srv = Server(m, params, ServeConfig(batch_slots=2, max_seq=32),
                 dtype=jnp.float32)
    r0 = Request(rid=0, prompt=prompt, max_tokens=5)
    r1 = Request(rid=1, prompt=np.array([1, 2], np.int32), max_tokens=3)
    srv.submit(r0)
    srv.submit(r1)
    srv.run_until_done()
    assert r0.out_tokens == toks
    assert len(r1.out_tokens) == 3


@pytest.mark.parametrize("temps", [(0.0, 0.0), (0.0, 0.8)], ids=["greedy", "greedy_and_hot"])
def test_server_picks_tokens_on_device(temps):
    cfg, m = _tiny()
    params = m.init(jax.random.key(0))
    prompts = [np.array([3, 7, 11, 2], np.int32), np.array([1, 2, 5], np.int32)]

    def direct(prompt, n):  # greedy, one row, argmax on the host
        caches = m.init_caches(1, 32, dtype=jnp.float32)
        lg, caches = m.prefill(params, jnp.asarray(prompt)[None], caches)
        toks = [int(jnp.argmax(lg[0, -1]))]
        while len(toks) < n:
            lg, caches = m.decode_step(params, jnp.asarray([[toks[-1]]]), caches)
            toks.append(int(jnp.argmax(lg[0, 0])))
        return toks

    def serve():
        srv = Server(m, params, ServeConfig(batch_slots=2, max_seq=32, seed=3),
                     dtype=jnp.float32)
        reqs = [Request(rid=i, prompt=p, max_tokens=6, temperature=t)
                for i, (p, t) in enumerate(zip(prompts, temps))]
        for r in reqs:
            srv.submit(r)
        srv.run_until_done()
        return srv, [r.out_tokens for r in reqs]

    srv, got = serve()
    assert got[0] == direct(prompts[0], 6)  # greedy slots: token for token
    # the other slot is greedy too, or draws off the greedy path
    assert (got[1] == direct(prompts[1], 6)) == (temps[1] <= 0.0)
    assert all(0 <= t < cfg.vocab for toks in got for t in toks)
    assert serve()[1] == got  # temperature slots repeat under the same seed
    assert srv.compiles == {"prefill": 2, "decode": 1}
    # other temperatures at the same prompt lengths retrace nothing
    for i, t in enumerate((1.3, 0.0)):
        srv.submit(Request(rid=10 + i, prompt=prompts[i], max_tokens=3, temperature=t))
    srv.run_until_done()
    assert srv.compiles == {"prefill": 2, "decode": 1}
    c = srv.counters
    assert c["sample_reads"] == c["prefill_calls"] + c["decode_ticks"]


def test_server_continuous_batching_refills():
    cfg, m = _tiny()
    params = m.init(jax.random.key(0))
    srv = Server(m, params, ServeConfig(batch_slots=2, max_seq=32),
                 dtype=jnp.float32)
    reqs = [Request(rid=i, prompt=np.array([i + 1], np.int32), max_tokens=3)
            for i in range(5)]
    for r in reqs:
        srv.submit(r)
    srv.run_until_done()
    assert all(r.done for r in reqs)
    assert all(len(r.out_tokens) == 3 for r in reqs)


SERVED = ["granite-3-2b", "mamba2-2.7b", "granite-4.0-h-small", "deepseek-v3-671b"]


def _full_batch_splice(live, fresh, slot):
    """The admission before one-row prefill: the slot's batch line of a
    full-batch tree copied into the live tree, the max of the lengths."""
    def put(path, a, b):
        name = path[-1].key
        if name == "length":
            return np.maximum(a, b)
        a = np.array(a)
        if a.ndim == serve_mod._CACHE_BASE_RANK[name]:
            a[slot] = b[slot]
        else:
            a[:, slot] = b[:, slot]
        return a

    return jax.tree_util.tree_map_with_path(put, live, fresh)


@pytest.mark.parametrize("arch", SERVED)
def test_server_one_row_admission(arch):
    """Each admission prefills its request alone and writes the row into
    its slot in place: the live cache after it equals the full-batch
    prefill and splice leaf by leaf, the splice compiles once for every
    slot and donates the live tree, and the tokens served equal the
    direct prefill and decode path.  Slots fill in the order 0, 1, 2,
    then refill as requests finish: 1, then 0.  Each refill's prompt is as
    long as the live cache, the one refill that the scalar ``length``
    serves exactly (the harness gives each wave fresh caches)."""
    cfg = get_config(arch, smoke=True)
    m = build_model(cfg)
    params = m.init(jax.random.key(0))
    slots, max_seq, plen = 3, 26, 5
    srv = Server(m, params, ServeConfig(batch_slots=slots, max_seq=max_seq),
                 dtype=jnp.float32)
    rng = np.random.default_rng(1)
    # the first wave frees slot 1, then slot 0; refills come in as their
    # slots free, each prompt as long as the live cache by then
    lens = [(plen, 3), (plen, 2), (plen, 4), (plen + 1, 3), (plen + 2, 2)]
    reqs = [Request(rid=i, prompt=rng.integers(1, cfg.vocab, n).astype(np.int32),
                    max_tokens=k) for i, (n, k) in enumerate(lens)]
    full_prefill = jax.jit(m.prefill)
    admitted = []
    spliced = serve_mod._splice_slot._cache_size()
    admit_one = srv._prefill_slot

    def checked(slot, req):
        before = jax.tree.map(np.array, srv.caches)
        old_leaves = jax.tree.leaves(srv.caches)
        toks = np.zeros((slots, len(req.prompt)), np.int32)
        toks[slot] = req.prompt
        _, fresh = full_prefill(params, jnp.asarray(toks),
                                m.init_caches(slots, max_seq, dtype=jnp.float32))
        want = _full_batch_splice(before, jax.tree.map(np.asarray, fresh), slot)
        admit_one(slot, req)
        assert all(leaf.is_deleted() for leaf in old_leaves)  # donated, written in place
        got = jax.tree_util.tree_flatten_with_path(srv.caches)[0]
        for (path, g), w in zip(got, jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(g), w, rtol=1e-5, atol=1e-6,
                                       err_msg=jax.tree_util.keystr(path))
        admitted.append(slot)

    srv._prefill_slot = checked
    first, refills = reqs[:slots], reqs[slots:]
    for r in first:
        srv.submit(r)
    while refills or not all(r.done for r in reqs):
        srv.step()
        if refills and any(a is None for a in srv.active):
            srv.submit(refills.pop(0))
    assert admitted == [0, 1, 2, 1, 0]
    assert serve_mod._splice_slot._cache_size() == spliced + 1  # one program, every slot

    decode_step = jax.jit(m.decode_step)

    def direct(prompt, n):  # greedy, one row, argmax on the host
        caches = m.init_caches(1, max_seq, dtype=jnp.float32)
        lg, caches = full_prefill(params, jnp.asarray(prompt)[None], caches)
        toks = [int(jnp.argmax(lg[0, -1]))]
        while len(toks) < n:
            lg, caches = decode_step(params, jnp.asarray([[toks[-1]]]), caches)
            toks.append(int(jnp.argmax(lg[0, 0])))
        return toks

    assert [r.out_tokens for r in reqs] == [direct(r.prompt, r.max_tokens) for r in reqs]
    assert srv.compiles == {"prefill": len({n for n, _ in lens}), "decode": 1}


@pytest.mark.parametrize("arch", SERVED)
def test_prefill_last_only_is_the_last_position(arch):
    cfg = get_config(arch, smoke=True)
    m = build_model(cfg)
    params = m.init(jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 7), 0, cfg.vocab)
    caches = m.init_caches(2, 16, dtype=jnp.float32)
    full, full_caches = m.prefill(params, tokens, caches)
    last, last_caches = m.prefill(params, tokens, caches, last_only=True)
    assert last.shape == (2, 1, cfg.padded_vocab)
    np.testing.assert_allclose(np.asarray(last), np.asarray(full[:, -1:]), rtol=1e-5, atol=1e-6)
    for a, b in zip(jax.tree.leaves(full_caches), jax.tree.leaves(last_caches)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_launch_serve_smoke(monkeypatch):
    from repro.launch import serve

    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    out = serve.main(["--arch", "granite-3-2b", "--smoke", "--requests", "3",
                      "--max-tokens", "3", "--slots", "2", "--max-seq", "32"])
    assert out["tokens"] == 9
    # two waves of slots: at least the 3 tokens of each, the first from prefill
    assert 4 <= out["ticks"] <= 3 * 3
