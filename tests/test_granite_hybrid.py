"""granite-4.0-h-small: Mamba2 and NoPE attention mixers, a 72-expert MoE
with a shared expert in every layer, granite's multipliers, and one chip's
share of the experts.

At a small width on the CPU, with the benchmark's configuration file and
seeded weights: the program served through ``Server`` against the plain
reference (``chipbench/reference/granite_moe_hybrid.py``), the expert
shares against the uncut layer, the new fields' neutral values against
their explicit ones, the parameter counts against the published sizes,
and the benchmark's run and MoE readers on this configuration."""

import dataclasses
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench import cells, harness, moe_scope, peaks, readings, weights  # noqa: E402
from chipbench import trace as T  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.models.moe import (  # noqa: E402
    MoEConfig, _shared_ffn, moe_apply_capacity, moe_apply_ragged, moe_defs, moe_ref)
from repro.models.params import init_params  # noqa: E402
from repro.runtime import Request, ServeConfig, Server  # noqa: E402

CELL = "granite-4.0-h-small.decode_heavy"
# every width cut to a size the CPU runs in seconds; the depth (10 layers,
# attention at 5), the router's 72 experts, the 9 held, the top 10 and
# the multipliers are the configuration file's own
SMALL = {"hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
         "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16, "mamba_chunk_size": 8,
         "intermediate_size": 32, "shared_intermediate_size": 48,
         "vocab_size": 500, "padded_vocab_size": 2048}
SMALL_TRAFFIC = {"slots": 3, "max_seq": 40, "prompt_buckets": [8, 16],
                 "output_buckets": [4, 8], "check_requests": 3}


def small_cell(**config):
    import copy

    cell = copy.deepcopy(cells.load_cell(CELL))
    cell.config.update(SMALL, **config)
    cell.traffic = dict(SMALL_TRAFFIC)
    # the small model's logits (an embedding drawn at 0.02 / 12, divided
    # by logits_scaling 16) spread a thousandth as far as granite-3-2b's at
    # full size: a sound bfloat16 run reads under 1e-4 here, the float8
    # control 4e-4 and more
    cell.limits = {"logit_gap_max": 2e-4}
    return cell


def f32_setup(seed=3):
    cell = small_cell(torch_dtype="float32")
    model, pcfg = harness.program_model(cell.config)
    family = cells.family_module(cell.config["family"])
    params = weights.make(model.abstract_params(), family.INIT, seed, pcfg.dtype,
                          cell.config["vocab_size"])
    return cell, model, params, family


def test_small_config_keeps_the_layout_and_the_share():
    cell, model, params, _ = f32_setup()
    cfg = model.cfg
    assert (cfg.n_layers, cfg.n_experts, cfg.n_experts_held, cfg.top_k) == (10, 72, 9, 10)
    kinds = [k.mixer for k in cfg.layout()]
    assert kinds == ["mamba"] * 5 + ["attn"] + ["mamba"] * 4
    # three segments: five stacked Mamba2 layers, the attention layer, four more
    stack = params["stack"]
    assert sorted(stack) == ["seg0", "seg1", "seg2"]
    assert stack["seg0"]["moe"]["w_gate"].shape == (5, 9, 64, 32)
    assert stack["seg1"]["moe"]["router"].shape == (64, 72)
    assert stack["seg1"]["moe"]["shared_w_up"].shape == (64, 48)


def test_server_prefill_then_decode_matches_the_reference():
    """Each request's prefill logits and every decode tick's logits, read
    from the server as it runs, against the reference's full forward pass
    over the served sequence.  Both in float32: the program's chunked SSD,
    flash attention and grouped expert matmuls against the reference's
    quadratic forms and dense experts agree to float32 rounding, 2e-5 of
    the logits' largest magnitude (a wrong held expert, scale or
    multiplier moves them by a tenth or more of it)."""
    cell, model, params, family = f32_setup()
    srv = Server(model, params, ServeConfig(batch_slots=3, max_seq=40), dtype=jnp.float32)
    seen = {0: [], 1: [], 2: []}  # slot -> the logits that chose its tokens
    prefill = srv._prefill_one
    admitted = iter(seen)  # free slots fill in order: request i in slot i

    def recording_prefill(p, toks, caches, temps, key):
        out = prefill(p, toks, caches, temps, key)  # the request's own row
        seen[next(admitted)].append(np.asarray(out[0][0, -1]))
        return out

    srv._prefill_one = recording_prefill
    rng = np.random.default_rng(0)
    # one wave of equal prompts, as the benchmark serves them (2 chunks of 8)
    reqs = [Request(rid=i, prompt=rng.integers(1, 500, 16).astype(np.int32), max_tokens=6)
            for i in range(2)]
    for r in reqs:
        srv.submit(r)
    while not all(r.done for r in reqs):
        srv.step()
        for slot, r in enumerate(reqs):
            if len(seen[slot]) < len(r.out_tokens):  # this step's decode tick served it
                seen[slot].append(np.asarray(srv.last_logits[slot, 0]))
    ref = family.logits_fn(cell.config)
    for slot, r in enumerate(reqs):
        seq = np.concatenate([r.prompt, r.out_tokens[:-1]]).astype(np.int32)
        rows = np.arange(len(r.prompt) - 1, len(seq))
        want = np.asarray(ref(params, jnp.asarray(seq), jnp.asarray(rows)))
        got = np.stack(seen[slot])
        assert got.shape == want.shape == (6, 2048)
        assert np.abs(got - want).max() <= 2e-5 * np.abs(want).max()


def test_expert_shares_sum_to_the_uncut_layer():
    """Eight chips' shares of a layer of 16 experts, 2 each: their outputs,
    the shared expert counted once, add up to the uncut layer."""
    cfg = MoEConfig(d_model=16, d_ff=32, n_experts=16, top_k=4, n_shared_experts=1,
                    shared_d_ff=24)
    params = init_params(moe_defs(cfg), jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, 12, 16))
    whole, _ = moe_ref(params, x, cfg)
    shared = _shared_ffn(params, x.reshape(-1, 16)).reshape(x.shape)
    parts = []
    for rank in range(8):
        share = dataclasses.replace(cfg, n_held=2, first_expert=2 * rank)
        held = {k: params[k][2 * rank:2 * rank + 2] for k in ("w_gate", "w_up", "w_down")}
        p = dict(params, **held)
        y, _ = moe_apply_ragged(p, x, share)
        parts.append(y)
        # the share's dense oracle agrees with its grouped path
        np.testing.assert_allclose(y, moe_ref(p, x, share)[0], atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(sum(parts) - 7 * shared, whole, atol=1e-5, rtol=1e-4)


def test_a_share_runs_on_the_ragged_path_only():
    cfg = MoEConfig(d_model=16, d_ff=32, n_experts=16, top_k=4, n_held=2, moe_impl="capacity")
    params = init_params(moe_defs(cfg), jax.random.key(0))
    with pytest.raises(NotImplementedError, match="ragged"):
        moe_apply_capacity(params, jnp.zeros((1, 4, 16)), cfg)


@pytest.mark.parametrize("arch", ["granite-3-2b", "granite-4.0-h-small"])
def test_neutral_fields_are_bit_exact(arch):
    """Every new field at its neutral value (no multiplier, the default
    score scale, every expert held, the shared width from d_ff) gives the
    logits, bit for bit, of the multipliers given as 1.0, the scale as
    1/sqrt(head_dim) and the expert fields spelled out."""
    base = get_config(arch, smoke=True)
    neutral = dataclasses.replace(
        base, attention_multiplier=None, embedding_multiplier=None, residual_multiplier=None,
        logits_scaling=None, n_experts_held=None, expert_offset=0, shared_d_ff=None)
    explicit = dataclasses.replace(
        neutral, attention_multiplier=1.0 / math.sqrt(base.head_dim_), embedding_multiplier=1.0,
        residual_multiplier=1.0, logits_scaling=1.0,
        n_experts_held=base.n_experts or None,
        shared_d_ff=base.d_ff * base.n_shared_experts or None)
    a, b = build_model(neutral), build_model(explicit)
    params = a.init(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, 8), 0, base.vocab)
    out = []
    for m in (a, b):
        caches = m.init_caches(2, 16, dtype=jnp.float32)
        lp, caches = m.prefill(params, toks, caches)
        ld, _ = m.decode_step(params, toks[:, :1], caches)
        out.append((np.asarray(lp), np.asarray(ld)))
    assert np.array_equal(out[0][0], out[1][0]) and np.array_equal(out[0][1], out[1][1])


def test_param_counts_match_the_published_and_the_cut():
    total, active = get_config("granite-4.0-h-small").param_counts()
    assert abs(total - 32e9) / 32e9 < 0.02  # 32B total
    assert abs(active - 9e9) / 9e9 < 0.05  # 9B active: 10 of 72 experts
    cell = cells.load_cell(CELL)
    model, pcfg = harness.program_model(cell.config)
    total, active = pcfg.param_counts()
    # the benchmark's cut: 2.41 B, of which 7.75 of 9 held experts idle a token
    assert total == 2_414_692_992
    assert total - active == 10 * 3 * 4096 * 768 * 9 * 62 // 72
    costs = cells.load_module(cells.HERE / "costs" / "granite_moe_hybrid.py")
    assert costs.decode(cell.config, 16, 1)[1] > 2 * total  # weights once, and the state
    assert costs._sizes(cell.config)["params"] == total


def small_run():
    return harness.run(small_cell(), 2**33 + 5, 2.0, False, time.time(), require_tpu=False)


def test_small_run_is_correct():
    res = small_run()
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0, res["checks"]


@pytest.mark.parametrize("fault", ["altered_token", "stale_state"])
def test_faulty_program_is_not_correct(fault, monkeypatch):
    """The check sees a served token moved to another id, and a decode step
    that returns the cache and state it was given."""
    from repro.models import model as M
    from repro.runtime import serve

    if fault == "altered_token":
        sample = serve.Server._sample

        def altered(self, pick, req):
            tok = sample(self, pick, req)
            return (tok + 101) % 500 if len(req.out_tokens) == 2 else tok

        monkeypatch.setattr(serve.Server, "_sample", altered)
    else:
        step = M.LM.decode_step

        def stale(self, params, tokens, caches):
            return step(self, params, tokens, caches)[0], caches

        monkeypatch.setattr(M.LM, "decode_step", stale)
    res = small_run()
    assert not res["correct"]
    assert res["checks"]["logit_gap_max"]["value"] > res["checks"]["logit_gap_max"]["limit"]


def test_control_reads_far_above_the_program():
    cell = small_cell()
    cell.traffic.update(slots=2, max_seq=80, prompt_buckets=[32, 64], output_buckets=[8, 16],
                        check_requests=4)
    row = readings.read_seed(cell, 11, "fp8", require_tpu=False)
    assert row["control"] > 3 * row["program"]
    assert row["program_correct"] and not row["control_correct"]


def test_moe_readers_sum_the_moe_scope_of_decode_ticks(monkeypatch):
    """On made-up device events named after the small decode step's own
    compiled HLO: the readers count the ops whose scope is the MoE's, per
    tick, and nothing in a window without decode ticks."""
    from chipbench import attribute as A

    cell = small_cell()
    text = moe_scope._decode_texts(cell.config, 16)[0]
    scopes = A.hlo_scopes(text)
    moe = [k for k, s in scopes.items() if A.layer(s) == "moe"]
    other = [k for k, s in scopes.items() if A.layer(s) in ("ssm", "attn")]
    assert {"route", "experts", "shared"} <= {p for k in moe for p in scopes[k].split("/")}
    mods, ops, t = [], [], 0.0
    for _ in range(2):  # two ticks of 7 ms: 3 MoE ops of 1 ms, 2 others of 2 ms
        mods.append(("jit_decode(1)", t, t + 0.007))
        start = t
        for key, dur in [(k, 0.001) for k in moe[:3]] + [(k, 0.002) for k in other[:2]]:
            name, shape = key.split(" ", 1)  # the trace's name: "%name = shape{layout} ..."
            ops.append((f"{name} = {shape}{{0}} op", start, start + dur))
            start += dur
        t += 0.010
    peak = peaks.PEAKS["TPU v5 lite"]

    def ctx(modules, ops):  # what the harness gives each reader of a run
        return {"trace": T.Trace({0: modules}, {0: ops}, [("step.decode", 0.0, t)]),
                "decode_kv": [1, 2], "prefill_plens": [], "slots": 16,
                "config": cell.config, "peaks": peak}

    run = ctx(mods, ops)
    assert cells.metric_reader("moe_device_ms.decode").read(run) == pytest.approx(3.0)
    costs = cells.load_module(cells.HERE / "costs" / "granite_moe_hybrid.py")
    f, b = costs.moe_decode(cell.config, 16)
    least = max(f / peak["flops_bf16"], b / peak["hbm_bytes_per_s"])
    got = cells.metric_reader("moe_roofline.decode").read(run)
    assert got == pytest.approx(100.0 * least / 0.003)
    idle = ctx([], [])
    assert cells.metric_reader("moe_device_ms.decode").read(idle) is None
    assert cells.metric_reader("moe_roofline.decode").read(idle) is None


# four instructions of the decode step as the TPU compiler writes it for a
# v5e (backend configs cut): a ragged_dot becomes custom calls named
# "ragged-dot-..." with no scope path, reading a copy of the expert weight
# that the layer loop makes under its own scope
TPU_HLO = """\
  %fusion.449 = bf16[160,4096]{1,0:T(8,128)(2,1)S(1)} fusion(%fusion.442, %pad_clamp_fusion.17), kind=kCustom, calls=%fused_computation.1.clone.clone, metadata={op_name="jit(decode)/stack/while/body/closed_call/checkpoint/moe/route/gather" stack_frame_id=8}
  %ragged-dot-metadata.1 = (s32[10]{0:T(128)S(1)}, s32[13]{0:T(128)S(1)}, s32[13]{0:T(128)S(1)}, s32[1]{0:T(128)}) custom-call(%fusion.447), custom_call_target="tpu_custom_call", operand_layout_constraints={s32[9]{0}}, metadata={op_name="ragged-dot-metadata"}
  %dynamic-slice_bitcast_fusion.13 = bf16[9,4096,768]{2,1,0:T(8,128)(2,1)S(1)} fusion(%get-tuple-element.1354, %get-tuple-element.1312), kind=kLoop, calls=%fused_computation.62.clone.clone, metadata={op_name="jit(decode)/stack/while/body/squeeze" stack_frame_id=8}
  %ragged-dot-none.4 = bf16[160,768]{1,0:T(8,128)(2,1)S(1)} custom-call(%get-tuple-element.1245, %get-tuple-element.1246, /*index=5*/%fusion.449, %dynamic-slice_bitcast_fusion.13), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
"""


def test_grouped_matmuls_and_their_weight_copies_are_the_moes():
    assert moe_scope.grouped_matmul_keys(TPU_HLO) == {
        "%ragged-dot-metadata.1 (s32[10]", "%ragged-dot-none.4 bf16[160,768]",
        "%dynamic-slice_bitcast_fusion.13 bf16[9,4096,768]"}  # not the tokens' gather
