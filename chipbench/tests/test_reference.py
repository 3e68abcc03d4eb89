"""Each plain reference against the program's own forward pass, in float32
on the CPU at a small size, with the benchmark's weights."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import cells, harness, weights

from conftest import small_cell


@pytest.mark.parametrize("workload", ["granite-3-2b.decode_heavy", "mamba2-2.7b.prefill_heavy"])
def test_reference_matches_model_apply(workload):
    cell = small_cell(workload)
    cell.config["torch_dtype"] = "float32"
    model, pcfg = harness.program_model(cell.config)
    family = cells.family_module(cell.config["family"])
    params = weights.make(model.abstract_params(), family.INIT, 3, pcfg.dtype, cell.config["vocab_size"])
    s = 32
    toks = np.random.default_rng(0).integers(0, cell.config["vocab_size"], s).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(model.apply(params, jnp.asarray(toks)[None])[0][0])
    rows = jnp.arange(s)
    got = np.asarray(family.logits_fn(cell.config)(params, jnp.asarray(toks), rows))
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()
    # padding ids score 0: their embedding rows are zero
    assert np.all(got[:, cell.config["vocab_size"]:] == 0)


def test_weights_are_drawn_from_the_seed():
    cell = small_cell("mamba2-2.7b.prefill_heavy")
    model, pcfg = harness.program_model(cell.config)
    family = cells.family_module(cell.config["family"])

    def make(seed):
        return weights.make(model.abstract_params(), family.INIT, seed, pcfg.dtype,
                            cell.config["vocab_size"])

    a, b, c = make(2**40 + 1), make(2**40 + 1), make(7)
    same = jax.tree.map(lambda x, y: bool(jnp.array_equal(x, y)), a, b)
    assert all(jax.tree.leaves(same))
    w = a["stack"]["seg0"]["mamba"]
    assert w["w_in"].dtype == jnp.bfloat16
    assert not jnp.array_equal(w["w_in"], c["stack"]["seg0"]["mamba"]["w_in"])
    a_neg = -jnp.exp(w["A_log"].astype(jnp.float32))
    assert float(a_neg.max()) < 0 and float(a_neg.min()) >= -16.5
