"""Jit'd public wrappers over the Pallas kernels.

``interpret`` is a required static argument: ``False`` compiles the
kernel for the TPU, ``True`` runs the Pallas interpreter (CPU tests and
rehearsals).  Nothing picks it from the backend, so a host whose TPU
failed to start errors instead of quietly interpreting.
"""

from __future__ import annotations

import functools

import jax

from . import flash as _flash
from . import gemm as _gemm
from . import gmm as _gmm
from . import gramschm as _gs
from . import histogram as _hist
from . import spmv as _spmv
from . import ssd as _ssd
from . import ttm as _ttm


@functools.partial(jax.jit, static_argnames=("interpret", "variant", "bm", "bn", "bk"))
def matmul(a, b, *, interpret: bool, variant: str = "v02", bm: int = 128,
           bn: int = 128, bk: int = 128):
    if variant == "v00":
        return _gemm.gemm_v00(a, b, interpret=interpret)
    if variant == "v01":
        return _gemm.gemm_v01(a, b, bm=8, interpret=interpret)
    return _gemm.gemm_v02(a, b, bm=bm, bn=bn, bk=bk, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret", "causal", "bq", "bkv"))
def flash_attention(q, k, v, *, interpret: bool, causal: bool = True,
                    bq: int = 128, bkv: int = 128):
    return _flash.flash_attention(
        q, k, v, causal=causal, bq=bq, bkv=bkv, interpret=interpret
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_chunk(x, a, bmat, cmat, *, interpret: bool):
    return _ssd.ssd_chunk(x, a, bmat, cmat, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret", "br"))
def spmv(vals, xg, *, interpret: bool, br: int = 8):
    return _spmv.spmv_ell(vals, xg, br=br, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret", "bf", "use_scratch"))
def ttm(vals, urows, *, interpret: bool, bf: int = 8, use_scratch: bool = False):
    return _ttm.ttm(vals, urows, bf=bf, use_scratch=use_scratch, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret", "k", "bj", "naive"))
def gramschm_k3(q_or_qt, a, *, interpret: bool, k: int = 0, bj: int = 128,
                naive: bool = True):
    fn = _gs.gramschm_k3_naive if naive else _gs.gramschm_k3_opt
    return fn(q_or_qt, a, k, bj=bj, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret", "n_bins", "block", "naive"))
def histogram(cells, n_bins: int, *, interpret: bool, block: int = 1024,
              naive: bool = False):
    fn = _hist.hist_naive if naive else _hist.hist_opt
    return fn(cells, n_bins, block=block, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret", "bm"))
def grouped_matmul(x, w, tile_expert_ids, *, interpret: bool, bm: int = 128):
    return _gmm.gmm(x, w, tile_expert_ids, bm=bm, interpret=interpret)
