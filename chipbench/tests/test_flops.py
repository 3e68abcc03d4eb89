"""``flops.py`` against the dot FLOPs that ``hlo_cost`` counts in the
compiled serving step, at a small size on the CPU."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from chipbench import flops, harness
from repro.core import hlo_cost

from conftest import small_cell

B, S = 3, 64  # slots, cache length


class DotCost(hlo_cost.HloCostModel):
    """``hlo_cost``'s walk of the compiled module (loops times their trip
    counts), counting the FLOPs of dots and convolutions only."""

    def _instr_cost(self, ins):
        c = super()._instr_cost(ins)
        if ins.op not in ("dot", "convolution"):
            c.flops = 0.0
        return c


def compiled_flops(fn, *args) -> float:
    return DotCost(jax.jit(fn).lower(*args).compile().as_text()).entry_cost().flops


def program(workload):
    cell = small_cell(workload, max_seq=S)
    # unembed over every row the program multiplies, padding included
    cell.config["vocab_size"] = cell.config["padded_vocab_size"]
    cell.config["torch_dtype"] = "float32"
    model, pcfg = harness.program_model(cell.config)
    return cell.config, model


def test_dense_decode_counts_every_dot():
    cfg, model = program("granite-3-2b.decode_heavy")
    p = model.abstract_params()
    c = model.init_caches(B, S, dtype=jnp.float32, abstract=True)
    t = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    # the program attends over its whole buffer: S positions
    want, _ = flops.decode(cfg, B, S)
    assert compiled_flops(model.decode_step, p, t, c) == pytest.approx(want, rel=1e-6)


def test_dense_prefill_is_one_causal_row_of_the_same_dots():
    cfg, model = program("granite-3-2b.decode_heavy")
    plen = 32
    p = model.abstract_params()
    c = model.init_caches(B, S, dtype=jnp.float32, abstract=True)
    t = jax.ShapeDtypeStruct((B, plen), jnp.int32)
    per_token, _ = flops.decode(cfg, 1, S)
    # the program computes every row of the batch, every position's
    # unembedding, and attention over its whole buffer
    got = compiled_flops(model.prefill, p, t, c)
    assert got == pytest.approx(B * plen * per_token, rel=1e-6)
    # the useful work: one row, causal pairs, the last position unembedded
    h, hd, layers = (cfg["num_attention_heads"], cfg["head_dim"], cfg["num_hidden_layers"])
    unembed = 2 * cfg["hidden_size"] * cfg["vocab_size"]
    no_attn = flops.decode(cfg, 1, 0)[0]
    useful = plen * (no_attn - unembed) + unembed + 4 * layers * h * hd * plen * (plen + 1) // 2
    assert flops.prefill(cfg, plen) == useful


def test_mamba_decode_counts_the_projections():
    cfg, model = program("mamba2-2.7b.prefill_heavy")
    p = model.abstract_params()
    c = model.init_caches(B, S, dtype=jnp.float32, abstract=True)
    t = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    got = compiled_flops(model.decode_step, p, t, c)
    want, _ = flops.decode(cfg, B, S)
    # flops.py counts the recurrence as 5 per state element (decay, outer
    # product, add; the C contraction); the compiled step writes 3 of
    # those as elementwise ops, which hlo_cost does not count as dots
    di = cfg["expand"] * cfg["d_model"]
    state = (di // cfg["headdim"]) * cfg["headdim"] * cfg["d_state"]
    elementwise = 3 * state * cfg["n_layer"] * B
    assert got == pytest.approx(want - elementwise, rel=1e-6)


def test_decode_bytes_are_weights_once_and_the_filled_cache():
    cfg, model = program("granite-3-2b.decode_heavy")
    weights = sum(x.size for x in jax.tree.leaves(model.abstract_params())) * 2
    kv_token = 2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"] * cfg["head_dim"] * 2
    assert flops.decode(cfg, B, 10)[1] == weights + B * 10 * kv_token
    assert dataclasses.is_dataclass(harness.Served)
