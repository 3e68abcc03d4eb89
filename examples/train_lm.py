"""End-to-end training driver: train a ~small LM for a few hundred steps
with checkpointing, preemption safety, straggler monitoring, and a
mid-run simulated restart (kill -> restore -> continue).

    PYTHONPATH=src python examples/train_lm.py [--steps 200]

On this CPU container the default model is ~100k params on synthetic
Zipf tokens; pass ``--arch granite-8b --smoke`` for an assigned-arch
smoke config, or run on a TPU fleet for the full config.
"""

import argparse
import os
import shutil
import tempfile

import jax
import jax.numpy as jnp

from repro.checkpoint import CheckpointManager
from repro.data import DataConfig, SyntheticSource, TokenPipeline
from repro.models import ModelConfig, build_model
from repro.optim import adamw, cosine_warmup
from repro.fault import StragglerMonitor
from repro.runtime.train_loop import TrainConfig, build_train_step, init_state, run


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--restart-at", type=int, default=None,
                    help="simulate a failure+restore at this step")
    args = ap.parse_args()
    steps = args.steps
    restart_at = args.restart_at or steps // 2

    cfg = ModelConfig(name="lm-demo", family="dense", n_layers=4, d_model=128,
                      n_heads=8, n_kv_heads=4, d_ff=512, vocab=2048,
                      dtype=jnp.float32)
    model = build_model(cfg)
    opt = adamw(cosine_warmup(3e-3, steps // 10, steps))
    tc = TrainConfig(grad_accum=2, max_grad_norm=1.0)
    dc = DataConfig(global_batch=16, seq_len=64, vocab=cfg.vocab, seed=0)

    ckpt_dir = tempfile.mkdtemp(prefix="repro_train_lm_")
    mgr = CheckpointManager(ckpt_dir, keep_n=2)
    monitor = StragglerMonitor()
    monitor.begin_step()

    def loss_fn(p, t, l):
        return model.loss(p, t, l)

    step = build_train_step(loss_fn, opt, tc)

    def state_tree(st):
        """Full restartable state: params + optimizer moments + step."""
        return {"params": st.params, "m": st.opt_state.m, "v": st.opt_state.v,
                "opt_step": st.opt_state.step}

    def make_hooks(pipe, captured):
        def capture(i, st, metrics):
            captured["state"] = st

        def ckpt(i, st, metrics):
            if (i + 1) % 25 == 0:
                mgr.save(state_tree(st), i + 1,
                         extra={"data_step": pipe.state()})

        def log(i, st, metrics):
            if i % 20 == 0:
                print(f"step {i:4d}  loss {float(metrics['loss']):.4f}  "
                      f"grad {float(metrics['grad_norm']):.3f}")

        return (capture, monitor.hook(), ckpt, log)

    # ---- phase 1: train until the simulated failure ----
    pipe = TokenPipeline(SyntheticSource(dc))
    state = init_state(model.init(jax.random.key(0)), opt, tc)
    captured = {}
    state, metrics = run(step, state, pipe, restart_at, make_hooks(pipe, captured))
    mgr.save(state_tree(state), restart_at,
             extra={"data_step": pipe.state()}, blocking=True)
    loss_at_kill = float(metrics["loss"])
    print(f"\n!! simulated preemption at step {restart_at} "
          f"(loss {loss_at_kill:.4f}); restarting from checkpoint...\n")

    # ---- phase 2: fresh process state, restore FULL state, continue ----
    target = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state_tree(state))
    restored, ck_step, extra = mgr.restore(target)
    pipe2 = TokenPipeline(SyntheticSource(dc))
    pipe2.restore(extra["data_step"])
    state2 = init_state(restored["params"], opt, tc)
    state2 = state2._replace(
        opt_state=state2.opt_state._replace(
            m=restored["m"], v=restored["v"], step=restored["opt_step"]))
    state2, metrics = run(step, state2, pipe2, steps - ck_step,
                          make_hooks(pipe2, {}), start_step=ck_step)
    print(f"\nfinal loss after restart: {float(metrics['loss']):.4f} "
          f"(was {loss_at_kill:.4f} at the kill point)")
    assert float(metrics["loss"]) < loss_at_kill + 0.35, "training regressed"
    print(f"straggler events observed: {len(monitor.events)}")
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    print("OK")


if __name__ == "__main__":
    main()
