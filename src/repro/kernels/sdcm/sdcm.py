"""Pallas TPU kernel for the SDCM conditional hit-rate (paper Eq. 1).

Evaluating P(h|D) for every reference of a multi-million-entry trace ×
several cache geometries is the compute hot spot of the prediction
pipeline (the paper re-implemented PPT-SASMM precisely because profile
math was slow).  The kernel evaluates the binomial CDF

    P(h|D) = sum_{k<A} C(D,k) p^k (1-p)^(D-k),   p = A/B

with the incremental log-space recurrence (log C(D,k) built by cumsum of
log((D-k+1)/k)), unrolled over k — A is a compile-time constant (<= 64
ways for every real cache), so the kernel is a fixed sequence of VPU
vector ops over an (8, 128) VMEM tile per grid step.

TPU adaptation notes: distances arrive as a flat f32 array reshaped to
(rows, 128) lanes; each grid step processes a (BLOCK_ROWS, 128) tile
held in VMEM.  No MXU use — this is a pure VPU kernel; the tile shape
is chosen to match the (8, 128) vreg layout.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.f32math import exp_f32, log_f32

LANES = 128
BLOCK_ROWS = 8  # (8, 128) = one f32 vreg tile


def _sdcm_kernel(d_ref, out_ref, *, assoc: int, log_p: float, log_1mp: float):
    d = d_ref[...]
    neg = d < 0.0  # INF_RD sentinel -> miss
    dd = jnp.maximum(d, 0.0)
    # k = 0 term: (1-p)^D
    acc = exp_f32(dd * log_1mp)
    log_comb = jnp.zeros_like(dd)
    for k in range(1, assoc):
        kf = float(k)
        log_comb = (log_comb + log_f32(jnp.maximum(dd - (kf - 1.0), 1e-30))
                    - math.log(kf))
        term = exp_f32(log_comb + kf * log_p + (dd - kf) * log_1mp)
        acc = acc + jnp.where(dd >= kf, term, 0.0)
    out = jnp.minimum(acc, 1.0)
    out = jnp.where(dd <= float(assoc - 1), 1.0, out)
    out_ref[...] = jnp.where(neg, 0.0, out).astype(out_ref.dtype)


def sdcm_pallas_2d(
    d2: jax.Array, assoc: int, blocks: int, *, interpret: bool = False
) -> jax.Array:
    """P(h|D) over a (rows, 128) f32 distance array (rows % 8 == 0)."""
    rows, lanes = d2.shape
    assert lanes == LANES and rows % BLOCK_ROWS == 0, d2.shape
    if not 1 <= assoc <= 64:
        raise ValueError("kernel supports 1 <= assoc <= 64 ways")
    p = assoc / blocks
    kernel = functools.partial(
        _sdcm_kernel,
        assoc=assoc,
        log_p=math.log(p),
        log_1mp=math.log1p(-p),
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(d2.shape, jnp.float32),
        grid=(rows // BLOCK_ROWS,),
        in_specs=[pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        interpret=interpret,
    )(d2)
