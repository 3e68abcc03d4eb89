"""Per-arch SMOKE tests: reduced same-family config, one forward + one
train step on CPU, asserting output shapes + no NaNs (the assignment's
required smoke matrix) — plus the registered ``cuthermo model`` configs
(transformer-tiny / moe-tiny / mamba-tiny): forward shape+dtype, grad
finiteness through the loss, and bit-exact determinism under a fixed
seed (the property whole-model profiling and its CI job lean on)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, SUBQUADRATIC, get_config
from repro.models import build_model
from repro.core.model_kernels import MODELS, get_model, model_names
from repro.optim import adamw, constant
from repro.runtime.train_loop import TrainConfig, build_train_step, init_state


@pytest.mark.parametrize("arch_id", ARCH_IDS + ["granite-4.0-h-small"])
def test_smoke_forward_and_train_step(arch_id):
    cfg = get_config(arch_id, smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    b, s = 2, 16
    tokens = jax.random.randint(jax.random.key(1), (b, s), 0, cfg.vocab)
    labels = jnp.roll(tokens, -1, axis=1)

    # forward
    if cfg.family == "audio":
        frames = jnp.zeros((b, 8, cfg.d_model), cfg.dtype)
        logits, _, aux = model.apply(params, tokens, embeddings=frames)
    else:
        logits, _, aux = model.apply(params, tokens)
    assert logits.shape == (b, s, cfg.padded_vocab)
    assert not bool(jnp.isnan(logits).any())

    # one real train step
    opt = adamw(constant(1e-3))

    def loss_fn(p, t, l):
        if cfg.family == "audio":
            fr = jnp.zeros((t.shape[0], 8, cfg.d_model), cfg.dtype)
            return model.loss(p, t, l, frames=fr)
        return model.loss(p, t, l)

    tc = TrainConfig()
    state = init_state(params, opt, tc)
    step = build_train_step(loss_fn, opt, tc, donate=False)
    state2, metrics = step(state, tokens, labels)
    assert np.isfinite(float(metrics["loss"]))
    # params actually changed
    delta = max(
        float(jnp.abs(a - b_).max())
        for a, b_ in zip(jax.tree.leaves(state.params), jax.tree.leaves(state2.params))
    )
    assert delta > 0


@pytest.mark.parametrize("arch_id", ["granite-8b", "mamba2-2.7b", "jamba-v0.1-52b",
                                     "deepseek-v3-671b", "whisper-base", "granite-4.0-h-small"])
def test_smoke_decode(arch_id):
    """Prefill + one decode step on the reduced config."""
    cfg = get_config(arch_id, smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    b = 2
    tokens = jax.random.randint(jax.random.key(1), (b, 8), 0, cfg.vocab)
    caches = model.init_caches(b, 32, dtype=jnp.float32)
    if cfg.family == "audio":
        frames = jnp.zeros((b, 8, cfg.d_model), cfg.dtype)
        logits, caches, _ = model.apply(params, tokens, caches=caches,
                                        embeddings=frames)
        logits, caches = model.decode_step(params, tokens[:, :1], caches,
                                           embeddings=frames)
    else:
        logits, caches = model.prefill(params, tokens, caches)
        logits, caches = model.decode_step(params, tokens[:, :1], caches)
    assert logits.shape[0] == b and logits.shape[1] == 1
    assert not bool(jnp.isnan(logits).any())


def test_layouts_match_assignment():
    """Layout structure sanity for the structured archs."""
    ds = get_config("deepseek-v3-671b")
    lo = ds.layout()
    assert len(lo) == 61
    assert all(k.mixer == "mla" for k in lo)
    assert [k.ffn for k in lo[:3]] == ["mlp"] * 3 and lo[3].ffn == "moe"

    jb = get_config("jamba-v0.1-52b")
    lo = jb.layout()
    assert len(lo) == 32
    assert sum(1 for k in lo if k.mixer == "attn") == 4  # 1:7 ratio
    assert sum(1 for k in lo if k.ffn == "moe") == 16  # every other layer
    assert lo[4].mixer == "attn"

    mb = get_config("mamba2-2.7b")
    assert all(k.mixer == "mamba" and k.ffn == "none" for k in mb.layout())

    gh = get_config("granite-4.0-h-small")
    lo = gh.layout()
    assert len(lo) == 40
    assert [i for i, k in enumerate(lo) if k.mixer == "attn"] == [5, 15, 25, 35]
    assert all(k.mixer == "mamba" for i, k in enumerate(lo) if i % 10 != 5)
    assert all(k.ffn == "moe" for k in lo)  # MoE in every layer


def test_param_counts_match_public_sizes():
    expect = {
        "granite-20b": (20.1e9, 0.06),
        "deepseek-v3-671b": (670.8e9, 0.02),
        "jamba-v0.1-52b": (51.2e9, 0.05),
        "mamba2-2.7b": (2.7e9, 0.1),
        "qwen2-vl-72b": (71.5e9, 0.05),
    }
    for arch, (want, tol) in expect.items():
        total, _ = get_config(arch).param_counts()
        assert abs(total - want) / want < tol, (arch, total)


def test_active_params_moe():
    total, active = get_config("granite-4.0-h-small").param_counts()
    assert 31e9 < total < 33e9 and 8.5e9 < active < 9.5e9  # 32B total, 9B active
    total, active = get_config("deepseek-v3-671b").param_counts()
    assert 35e9 < active < 40e9  # paper: 37B activated
    total, active = get_config("llama4-scout-17b-a16e").param_counts()
    assert 14e9 < active < 19e9  # ~17B activated


# ---------------------------------------------------------------------------
# the registered `cuthermo model` configs
# ---------------------------------------------------------------------------


def _model_batch(name):
    entry = get_model(name)
    model = build_model(entry.config)
    params = model.init(jax.random.key(0))
    tokens = jax.random.randint(
        jax.random.key(1), (entry.batch, entry.seq), 0, entry.config.vocab
    )
    return entry, model, params, tokens


@pytest.mark.parametrize("name", model_names())
def test_registered_model_forward_shape_and_dtype(name):
    entry, model, params, tokens = _model_batch(name)
    cfg = entry.config
    logits, _, _ = model.apply(params, tokens)
    assert logits.shape == (entry.batch, entry.seq, cfg.padded_vocab)
    assert logits.dtype == cfg.dtype
    assert bool(jnp.isfinite(logits).all())


@pytest.mark.parametrize("name", model_names())
def test_registered_model_grads_are_finite(name):
    entry, model, params, tokens = _model_batch(name)
    labels = jnp.roll(tokens, -1, axis=1)

    def scalar_loss(p):
        loss, _aux = model.loss(p, tokens, labels)
        return loss

    loss, grads = jax.value_and_grad(scalar_loss)(params)
    assert np.isfinite(float(loss))
    leaves = jax.tree.leaves(grads)
    assert leaves, "loss produced an empty grad tree"
    for g in leaves:
        assert bool(jnp.isfinite(g).all())
    # the loss actually depends on the parameters
    assert any(float(jnp.abs(g).max()) > 0 for g in leaves)


@pytest.mark.parametrize("name", model_names())
def test_registered_model_forward_is_deterministic(name):
    # same seed, fresh params and fresh apply: bit-identical logits —
    # the invariant the `model-smoke` CI job's cached rerun relies on
    _, _, params_a, tokens_a = _model_batch(name)
    _, model, params_b, tokens_b = _model_batch(name)
    assert np.array_equal(np.asarray(tokens_a), np.asarray(tokens_b))
    for a, b in zip(jax.tree.leaves(params_a), jax.tree.leaves(params_b)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    la, _, _ = model.apply(params_a, tokens_a)
    lb, _, _ = model.apply(params_b, tokens_b)
    assert np.array_equal(np.asarray(la), np.asarray(lb))


def test_registered_model_shapes_are_ci_sized():
    # the registry promises CI-scale models; a config growth that would
    # blow up the model-smoke job budget should fail here first
    for name, entry in MODELS.items():
        cfg = entry.config
        assert cfg.n_layers <= 4, name
        assert cfg.d_model <= 256, name
        assert entry.batch * entry.seq <= 512, name
