"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Run:
    PYTHONPATH=src python -m repro.launch.dryrun --arch granite-8b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

For each cell this:
  1. builds the production mesh (16x16 or 2x16x16 placeholder devices),
  2. builds ABSTRACT params/optimizer/caches (ShapeDtypeStruct — zero
     allocation; a 671B model costs no host memory),
  3. jit-lowers the train_step / prefill / serve_step with full
     in/out shardings, compiles it,
  4. records memory_analysis (proves fit), cost_analysis (FLOPs/bytes),
     and the Level-3 collective-byte walk of the compiled HLO
     to artifacts/dryrun/<mesh>/<arch>__<shape>.json.

Sharding policy (see DESIGN.md §4): DP over (pod,data), ZeRO-3/FSDP
params over the data axes, TP over model, EP experts over model, SP for
activations (train) and cache sequence (decode).
"""

# The VERY FIRST lines — before ANY other import — since jax locks the
# device count on first init:
import os  # noqa: E402

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
from typing import Any, Dict, Optional, Tuple  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import SHAPES, all_cells, get_config, skipped_cells  # noqa: E402
from repro.core import hlo_cost, hlo_thermo, roofline  # noqa: E402
from repro.launch.mesh import data_axes_of, make_production_mesh, n_chips  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.models import params as PM  # noqa: E402
from repro.optim import adamw, cosine_warmup  # noqa: E402
from repro.parallel.sharding import (  # noqa: E402
    cache_specs,
    fixup_specs,
    make_rules,
    specs_from_logical,
)
from repro.runtime.train_loop import TrainConfig, TrainState, build_train_step  # noqa: E402

ARTIFACTS = os.path.join(os.path.dirname(__file__), "..", "..", "..", "artifacts", "dryrun")


def build_rules(mesh, shape_kind: str, sp: bool = True,
                weight_stationary: bool = False,
                data_axes_override=None, expert_axes=None):
    """Sharding rules per shape kind.

    ``weight_stationary`` (serving, when params fit TP-only): replicate
    weights across the data axes instead of FSDP — kills the per-token
    weight all-gathers that made decode collective-bound (measured 2.7x
    on granite-8b decode_32k).
    """
    data_axes = data_axes_override or data_axes_of(mesh)
    return make_rules(
        data_axes=data_axes,
        fsdp=not weight_stationary,
        fsdp_axes=data_axes,  # ZeRO-3: params sharded over every data axis
        expert_axes=expert_axes,
        seq_shard_cache=(shape_kind == "decode"),
        extra=(
            (("act_seq", ("model",)),)
            if sp and shape_kind == "train"
            else ()
        ),
    )


# serving is weight-stationary when TP-only params fit comfortably in HBM
_WS_HBM_BUDGET = 8 * 1024**3  # bf16 params per chip, model-axis sharded


def _named(tree_specs, mesh):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree_specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def input_specs(arch_id: str, shape_name: str, opt_state_dtype: str = "f32",
                smoke: bool = False) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for every input of the cell's step fn."""
    cfg = get_config(arch_id, smoke=smoke)
    shape = SHAPES[shape_name]
    if cfg.n_experts and shape.kind in ("train", "prefill") and not smoke:
        # explicit-all-to-all expert parallelism for the big token counts
        # (the GSPMD-routed capacity path is ~10x wire bytes — §Perf)
        cfg = dataclasses.replace(cfg, moe_impl="ep")
    model = build_model(cfg)
    b, s = shape.global_batch, shape.seq_len
    out: Dict[str, Any] = {"config": cfg, "model": model, "shape": shape}
    if shape.kind == "train":
        out["tokens"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
        out["labels"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
    elif shape.kind == "prefill":
        # cache capacity == prompt length: the prefill write is a clean
        # full-buffer replacement (partitions on any sharding)
        out["tokens"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
        out["caches"] = model.init_caches(b, s, dtype=jnp.bfloat16, abstract=True)
    else:  # decode: one new token against a seq_len-deep cache
        out["tokens"] = jax.ShapeDtypeStruct((b, 1), jnp.int32)
        out["caches"] = model.init_caches(b, s, dtype=jnp.bfloat16, abstract=True)
    if cfg.family == "audio":
        frames = min(cfg.max_source_positions, 1500)
        out["frames"] = jax.ShapeDtypeStruct((b, frames, cfg.d_model), cfg.dtype)
    return out


def build_cell(arch_id: str, shape_name: str, mesh, *, sp: bool = True,
               opt_state_dtype: str = "f32", smoke: bool = False):
    """Returns (jitted_fn, arg_specs, model_flops, meta); meta['_rules']
    carries the Rules used (for use_rules at lower time)."""
    spec = input_specs(arch_id, shape_name, opt_state_dtype, smoke=smoke)
    cfg, model, shape = spec["config"], spec["model"], spec["shape"]
    chips = n_chips(mesh)
    data_axes = data_axes_of(mesh)
    weight_stationary = False
    if shape.kind in ("prefill", "decode"):
        total, _ = cfg.param_counts()
        weight_stationary = (total * 2 / mesh.shape["model"]) < _WS_HBM_BUDGET
    # pure-DP fallback (batch over the model axis too) — HYPOTHESIS
    # REFUTED for whisper (90 -> 406 GiB; the real culprit was the
    # unconstrained embedding-gather output, see EXPERIMENTS.md §Perf);
    # kept as an explicit experiment knob only.
    msize = mesh.shape["model"]
    pure_dp = bool(int(os.environ.get("REPRO_PURE_DP", "0"))) and (
        shape.kind == "train"
        and shape.global_batch % (_axes_size(mesh, data_axes) * msize) == 0
    )
    if pure_dp:
        data_axes = data_axes + ("model",)
    # widest expert placement that divides the expert count: spanning the
    # data axes makes experts device-local (no FSDP gather / grad reduce
    # for the expert bank — deepseek train went 6.2 -> 0.6 TB wire)
    expert_axes = None
    if cfg.n_experts:
        for cand in (("model",) + data_axes, ("model",) + data_axes[-1:],
                     ("model",)):
            size = 1
            for a in cand:
                size *= mesh.shape[a]
            if cfg.n_experts % size == 0:
                expert_axes = cand
                break
    rules = build_rules(mesh, shape.kind, sp=sp and not pure_dp,
                        weight_stationary=weight_stationary,
                        data_axes_override=data_axes,
                        expert_axes=expert_axes)

    # params: logical -> physical (+ divisibility fixup)
    abstract = model.abstract_params()
    pspecs = fixup_specs(
        specs_from_logical(model.logical_specs(), rules), abstract, mesh
    )
    psh = _named(pspecs, mesh)

    # activation constraint (sequence-parallel residual stream)
    if shape.kind == "train" and sp and hasattr(model, "stack_cfg"):
        act_spec = rules.spec(("act_batch", "act_seq", None))

        def act_constraint(x):
            return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, act_spec))

        model.stack_cfg = dataclasses.replace(
            model.stack_cfg, act_constraint=act_constraint
        )

    bspec = (
        data_axes
        if shape.global_batch % _axes_size(mesh, data_axes) == 0
        else None
    )
    tok_sh = NamedSharding(mesh, P(bspec, None))

    meta: Dict[str, Any] = {
        "arch": arch_id, "shape": shape_name, "kind": shape.kind,
        "chips": chips, "mesh": "x".join(map(str, mesh.devices.shape)),
        "pure_dp": pure_dp, "weight_stationary": weight_stationary,
        "_rules": rules,
    }

    if shape.kind == "train":
        total, active = cfg.param_counts()
        model_flops = cfg.model_flops_train(shape.global_batch, shape.seq_len)
        opt = adamw(cosine_warmup(3e-4, 2000, 100_000), state_dtype=opt_state_dtype)

        batch_spec = NamedSharding(mesh, P(bspec, None, None))

        def loss_fn(params, tokens, labels):
            if cfg.family == "audio":
                b = tokens.shape[0]
                frames = jnp.zeros(
                    (b, min(cfg.max_source_positions, 1500), cfg.d_model), cfg.dtype
                )
                # shard the synthetic frames like real data would be —
                # otherwise the encoder runs replicated on every chip
                frames = jax.lax.with_sharding_constraint(frames, batch_spec)
                return model.loss(params, tokens, labels, frames=frames)
            return model.loss(params, tokens, labels)

        # abstract TrainState
        mspec = pspecs if opt_state_dtype != "int8" else pspecs
        m_abs = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape,
                {"f32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8}[
                    opt_state_dtype
                ],
            ),
            abstract,
        )
        scale_abs = (
            jax.tree.map(lambda a: jax.ShapeDtypeStruct((), jnp.float32), abstract)
            if opt_state_dtype == "int8"
            else None
        )
        from repro.optim.optimizers import OptState

        state_abs = TrainState(
            params=abstract,
            opt_state=OptState(
                step=jax.ShapeDtypeStruct((), jnp.int32),
                m=m_abs, v=m_abs, mu=scale_abs, nu=scale_abs,
            ),
            err_buffer=None,
        )
        scale_sh = (
            jax.tree.map(lambda _: NamedSharding(mesh, P()), abstract)
            if opt_state_dtype == "int8"
            else None
        )
        state_sh = TrainState(
            params=psh,
            opt_state=OptState(
                step=NamedSharding(mesh, P()), m=psh, v=psh, mu=scale_sh, nu=scale_sh
            ),
            err_buffer=None,
        )
        step = build_train_step(
            loss_fn,
            opt,
            TrainConfig(grad_accum=1),
            donate=True,
            in_shardings=(state_sh, tok_sh, tok_sh),
        )
        args = (state_abs, spec["tokens"], spec["labels"])
        meta.update(total_params=total, active_params=active)
        return step, args, model_flops, meta

    # serving paths
    total, active = cfg.param_counts()
    cspecs = fixup_specs(
        cache_specs(spec["caches"], rules, mesh), spec["caches"], mesh
    )
    csh = _named(cspecs, mesh)
    if shape.kind == "prefill":
        model_flops = 2.0 * active * shape.global_batch * shape.seq_len

        if cfg.family == "audio":
            def fn(params, tokens, caches, frames):
                logits, new_caches, _ = model.apply(
                    params, tokens, caches=caches, embeddings=frames
                )
                return logits[:, -1:], new_caches

            fr_sh = NamedSharding(mesh, P(bspec, None, None))
            jfn = jax.jit(fn, in_shardings=(psh, tok_sh, csh, fr_sh),
                          donate_argnums=(2,))
            args = (abstract, spec["tokens"], spec["caches"], spec["frames"])
        else:
            def fn(params, tokens, caches):
                logits, new_caches, _ = model.apply(
                    params, tokens, caches=caches, last_only=True
                )
                return logits, new_caches

            jfn = jax.jit(fn, in_shardings=(psh, tok_sh, csh), donate_argnums=(2,))
            args = (abstract, spec["tokens"], spec["caches"])
    else:  # decode
        model_flops = 2.0 * active * shape.global_batch

        if cfg.family == "audio":
            enc_abs = jax.ShapeDtypeStruct(
                (shape.global_batch, min(cfg.max_source_positions, 1500), cfg.d_model),
                cfg.dtype,
            )

            def fn(params, tokens, caches, enc):
                logits, new_caches = model.decode(params, tokens, enc, caches,
                                                  start=_cache_len(caches))
                return logits, new_caches

            enc_sh = NamedSharding(mesh, P(bspec, None, None))
            jfn = jax.jit(fn, in_shardings=(psh, tok_sh, csh, enc_sh),
                          donate_argnums=(2,))
            args = (abstract, spec["tokens"], spec["caches"], enc_abs)
        else:
            def fn(params, tokens, caches):
                return model.decode_step(params, tokens, caches)

            jfn = jax.jit(fn, in_shardings=(psh, tok_sh, csh), donate_argnums=(2,))
            args = (abstract, spec["tokens"], spec["caches"])
    meta.update(total_params=total, active_params=active)
    return jfn, args, model_flops, meta


def _cache_len(caches):
    from repro.models.model import caches_length

    return caches_length(caches)


def _axes_size(mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def run_cell(arch_id: str, shape_name: str, multi_pod: bool, *, sp: bool = True,
             opt_state_dtype: str = "f32", out_dir: Optional[str] = None,
             verbose: bool = True) -> Dict[str, Any]:
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = n_chips(mesh)
    t0 = time.time()
    fn, args, model_flops, meta = build_cell(
        arch_id, shape_name, mesh, sp=sp, opt_state_dtype=opt_state_dtype
    )
    from repro.parallel.context import use_rules

    rules = meta.pop("_rules")
    with mesh, use_rules(rules, mesh):
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        t1 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t1
        mem = hlo_thermo.memory_analysis_dict(compiled)
        xla_cost = hlo_thermo.cost_analysis_dict(compiled)
        hlo_text = compiled.as_text()
        # trip-count-aware costs (XLA's cost_analysis counts scanned layer
        # bodies ONCE — see core/hlo_cost.py); all numbers are per-device
        cost = hlo_cost.analyze(hlo_text, total_devices=chips)
    terms = roofline.RooflineTerms(
        name=f"{arch_id}/{shape_name}",
        device_kind=roofline.V5E,  # modeled: the CPU stands in for v5e
        chips=chips,
        hlo_flops=cost.flops,
        hlo_bytes=cost.bytes,
        collective_bytes=cost.wire_bytes,
        model_flops=model_flops,
    )
    result = {
        **meta,
        "ok": True,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": mem,
        "per_device_bytes": sum(
            mem.get(k, 0.0)
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes")
        ) - mem.get("alias_size_in_bytes", 0.0),
        "cost": {"flops": cost.flops, "bytes": cost.bytes},
        "xla_cost_singlecount": {
            k: xla_cost[k] for k in ("flops", "bytes accessed") if k in xla_cost
        },
        "collectives": {
            "total_wire_bytes_per_device": cost.wire_bytes,
            "by_op": dict(cost.by_collective),
        },
        "model_flops": model_flops,
        "roofline": terms.as_dict(),
        "bound": terms.bound,
    }
    if verbose:
        hbm = result["per_device_bytes"] / 2**30
        print(
            f"[dryrun] {arch_id:>22s} x {shape_name:<12s} mesh={meta['mesh']:<8s} "
            f"lower {t_lower:5.1f}s compile {t_compile:6.1f}s | "
            f"{hbm:7.2f} GiB/chip | {terms.summary()}"
        )
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir, f"{arch_id}__{shape_name}.json")
        with open(path, "w") as f:
            json.dump(result, f, indent=1)
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--no-sp", action="store_true")
    ap.add_argument("--opt-state-dtype", default="f32",
                    choices=["f32", "bf16", "int8"])
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    cells = all_cells() if args.all else [(args.arch, args.shape)]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    failures = []
    for multi in meshes:
        mesh_name = "multi_2x16x16" if multi else "single_16x16"
        out_dir = args.out or os.path.normpath(
            os.path.join(os.path.dirname(__file__), "..", "..", "..",
                         "artifacts", "dryrun", mesh_name)
        )
        for arch_id, shape_name in cells:
            if arch_id is None or shape_name is None:
                raise SystemExit("--arch/--shape required unless --all")
            try:
                run_cell(
                    arch_id, shape_name, multi, sp=not args.no_sp,
                    opt_state_dtype=args.opt_state_dtype, out_dir=out_dir,
                )
            except Exception as e:  # noqa: BLE001 — report, continue, fail at end
                failures.append((mesh_name, arch_id, shape_name, repr(e)[:300]))
                print(f"[dryrun] FAIL {arch_id} x {shape_name} ({mesh_name}): {e}")
    skips = skipped_cells()
    print(f"\n[dryrun] done: {len(cells)*len(meshes)-len(failures)} ok, "
          f"{len(failures)} failed, {len(skips)} skipped-by-design "
          f"(long_500k on full-attention archs)")
    if failures:
        for f in failures:
            print("  FAIL:", *f)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
