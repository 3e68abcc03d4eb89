"""Training runtime: TrainState, step builder, grad accumulation, hooks.

The step builder returns a jit-compiled ``train_step(state, tokens,
labels) -> (state, metrics)`` with:

  * gradient accumulation over ``grad_accum`` microbatches via lax.scan —
    the data-axis all-reduce happens ONCE on the accumulated gradient
    (deferred-psum: under SPMD the reduce materializes where the grads
    meet the replicated optimizer math, i.e. after the scan);
  * optional gradient compression (bf16/int8 + error feedback) applied
    to the accumulated gradient before it crosses the data axis;
  * global-norm clipping, schedule-driven optimizer, aux-loss plumbing;
  * donated state (in-place buffers on TPU).

Hooks (thermo profiling, straggler monitor, checkpointing) observe each
step from the host side — see ``repro.fault`` and the examples.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.optim import Optimizer, OptState, clip_by_global_norm
from repro.parallel.compression import (
    CompressionConfig,
    compress,
    decompress,
    init_error_buffer,
)

PyTree = Any


class TrainState(NamedTuple):
    params: PyTree
    opt_state: OptState
    err_buffer: Optional[PyTree] = None  # compression error feedback


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    grad_accum: int = 1
    max_grad_norm: float = 1.0
    compression: CompressionConfig = CompressionConfig()


def init_state(
    params: PyTree, optimizer: Optimizer, cfg: TrainConfig = TrainConfig()
) -> TrainState:
    return TrainState(
        params=params,
        opt_state=optimizer.init(params),
        err_buffer=init_error_buffer(params, cfg.compression),
    )


def build_train_step(
    loss_fn: Callable[[PyTree, jax.Array, jax.Array], Tuple[jax.Array, Dict]],
    optimizer: Optimizer,
    cfg: TrainConfig = TrainConfig(),
    donate: bool = True,
    in_shardings: Any = None,
    out_shardings: Any = None,
):
    """loss_fn(params, tokens, labels) -> (loss, metrics dict)."""

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def step(state: TrainState, tokens: jax.Array, labels: jax.Array):
        if cfg.grad_accum > 1:
            b = tokens.shape[0]
            assert b % cfg.grad_accum == 0
            mb = b // cfg.grad_accum
            tok_mb = tokens.reshape(cfg.grad_accum, mb, *tokens.shape[1:])
            lab_mb = labels.reshape(cfg.grad_accum, mb, *labels.shape[1:])

            def accum(carry, xs):
                g_acc, loss_acc = carry
                t, l = xs
                (loss, metrics), g = grad_fn(state.params, t, l)
                g_acc = jax.tree.map(
                    lambda a, b_: a + b_.astype(jnp.float32), g_acc, g
                )
                return (g_acc, loss_acc + loss), metrics

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params
            )
            (g_sum, loss_sum), metrics = jax.lax.scan(
                accum, (g0, jnp.zeros((), jnp.float32)), (tok_mb, lab_mb)
            )
            grads = jax.tree.map(lambda g: g / cfg.grad_accum, g_sum)
            loss = loss_sum / cfg.grad_accum
            metrics = jax.tree.map(lambda m: m[-1], metrics)
        else:
            (loss, metrics), grads = grad_fn(state.params, tokens, labels)

        # gradient compression before the data-axis reduce
        err = state.err_buffer
        wire, new_err = compress(grads, err, cfg.compression)
        grads = decompress(wire, cfg.compression)

        grads, gnorm = clip_by_global_norm(grads, cfg.max_grad_norm)
        new_params, new_opt = optimizer.update(grads, state.opt_state, state.params)
        metrics = dict(metrics)
        metrics["grad_norm"] = gnorm
        metrics["loss"] = loss
        return TrainState(new_params, new_opt, new_err), metrics

    kwargs = {}
    if in_shardings is not None:
        kwargs["in_shardings"] = in_shardings
    if out_shardings is not None:
        kwargs["out_shardings"] = out_shardings
    return jax.jit(step, donate_argnums=(0,) if donate else (), **kwargs)


def run(
    train_step,
    state: TrainState,
    pipeline,
    n_steps: int,
    hooks: Tuple[Callable[[int, TrainState, Dict], None], ...] = (),
    start_step: int = 0,
) -> Tuple[TrainState, Dict]:
    """Host-side loop: data -> step -> hooks. Returns final (state, metrics)."""
    metrics: Dict = {}
    it = iter(pipeline)
    for i in range(start_step, start_step + n_steps):
        tokens, labels = next(it)
        state, metrics = train_step(state, jnp.asarray(tokens), jnp.asarray(labels))
        for h in hooks:
            h(i, state, metrics)
    return state, metrics
