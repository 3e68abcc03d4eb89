"""Precision of the kernels' matrix products on the MXU."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def dot_precision(dtype) -> Optional[jax.lax.Precision]:
    """``HIGHEST`` for f32 operands, the default (``None``) otherwise.

    At its default precision Mosaic multiplies f32 operands in one bf16
    pass; ``HIGHEST`` makes an f32 kernel agree with an f32 oracle.
    Mosaic refuses ``HIGHEST`` for bf16 operands, which need one pass.
    """
    return jax.lax.Precision.HIGHEST if jnp.dtype(dtype) == jnp.float32 else None
