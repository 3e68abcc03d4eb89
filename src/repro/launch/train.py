"""Training launcher: end-to-end distributed training driver.

    PYTHONPATH=src python -m repro.launch.train --arch granite-8b --smoke \
        --steps 50 --ckpt-dir /tmp/ckpt

On this CPU container use ``--smoke`` (reduced configs, real compute).
On a TPU fleet the same script runs the full config: the mesh comes from
``jax.devices()``, data is sharded per host, checkpoints restore
elastically, SIGTERM triggers an emergency checkpoint.
"""

from __future__ import annotations

import argparse
import os
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.data import DataConfig, SyntheticSource, TokenPipeline
from repro.models import build_model
from repro.optim import adamw, cosine_warmup
from repro.parallel.sharding import fixup_specs, make_rules, specs_from_logical
from repro.runtime import (
    PreemptionHandler,
    StragglerMonitor,
    TrainConfig,
    build_train_step,
    init_state,
    run,
)
from repro.runtime.train_loop import TrainState


def make_mesh_from_devices():
    devs = jax.devices()
    n = len(devs)
    if n == 1:
        return None
    # squarest (data, model) factorization
    for m in range(int(n**0.5), 0, -1):
        if n % m == 0:
            return make_mesh((n // m, m), ("data", "model"))
    return None


def main(argv=None) -> Dict[str, Any]:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    model = build_model(cfg)
    mesh = make_mesh_from_devices()

    opt = adamw(cosine_warmup(args.lr, max(args.steps // 10, 1), args.steps))
    tc = TrainConfig(grad_accum=args.grad_accum)

    params = model.init(jax.random.key(args.seed))
    if mesh is not None:
        rules = make_rules(data_axes=("data",), fsdp=True)
        pspecs = fixup_specs(
            specs_from_logical(model.logical_specs(), rules), params, mesh
        )
        psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                           is_leaf=lambda x: isinstance(x, P))
        params = jax.tree.map(jax.device_put, params, psh)
    state = init_state(params, opt, tc)

    def loss_fn(p, t, l):
        if cfg.family == "audio":
            frames = jnp.zeros(
                (t.shape[0], min(cfg.max_source_positions, 64), cfg.d_model),
                cfg.dtype,
            )
            return model.loss(p, t, l, frames=frames)
        return model.loss(p, t, l)

    step = build_train_step(loss_fn, opt, tc)

    dc = DataConfig(global_batch=args.batch, seq_len=args.seq, vocab=cfg.vocab,
                    seed=args.seed)
    pipe = TokenPipeline(SyntheticSource(dc))

    hooks = []
    monitor = StragglerMonitor()
    monitor.begin_step()
    hooks.append(monitor.hook())

    start_step = 0
    mgr: Optional[CheckpointManager] = None
    if args.ckpt_dir:
        mgr = CheckpointManager(args.ckpt_dir, keep_n=3)
        if args.resume and mgr.latest_step() is not None:
            target = {
                "params": jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state.params
                )
            }
            restored, ck_step, extra = mgr.restore(target)
            state = state._replace(params=restored["params"])
            start_step = ck_step
            pipe.restore(extra.get("data_step", ck_step))
            print(f"[train] resumed from step {ck_step}")

        def ckpt_hook(i, st, metrics):
            if (i + 1) % args.ckpt_every == 0:
                mgr.save({"params": st.params}, i + 1,
                         extra={"data_step": pipe.state()})

        hooks.append(ckpt_hook)
        pre = PreemptionHandler().register()
        hooks.append(
            pre.checkpoint_hook(
                mgr, lambda: ({"params": state.params}, {"data_step": pipe.state()})
            )
        )

    def log_hook(i, st, metrics):
        if i % 10 == 0 or i == start_step + args.steps - 1:
            print(
                f"[train] step {i:5d} loss {float(metrics['loss']):.4f} "
                f"grad_norm {float(metrics['grad_norm']):.3f}"
            )

    hooks.append(log_hook)

    ctx = mesh if mesh is not None else _nullcontext()
    with ctx:
        state, metrics = run(step, state, pipe, args.steps, tuple(hooks),
                             start_step=start_step)
    if mgr:
        mgr.save({"params": state.params}, start_step + args.steps,
                 extra={"data_step": pipe.state()}, blocking=True)
    return {"final_loss": float(metrics["loss"]), "steps": args.steps,
            "straggler_events": len(monitor.events)}


class _nullcontext:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    out = main()
    print("[train] done:", out)
