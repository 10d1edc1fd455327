"""Pallas TPU kernel: log2-binned weighted reuse-profile histogram.

Builds the reuse profile P(D) (paper Table 2 / §3.3.1) from a raw
distance stream.  Scatter-adds are hostile to the TPU vector unit, so
the kernel turns binning into a dense one-hot contraction: each (8,128)
tile of distances becomes a (TILE, BINS) one-hot matrix folded into the
per-bin accumulator — an MXU-friendly reformulation of a histogram.

Bin layout: bin 0 <- INF_RD (first touch / D = inf);
            bin b <- finite D with floor(log2(max(D,1))) == b-1 ... i.e.
            b = 1 + ceil-log2 bucket, clamped to BINS-1.

The output block index_map pins every grid step to the same (1, BINS)
accumulator block; step 0 initializes it (the canonical Pallas
accumulation pattern over a sequential grid).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
BLOCK_ROWS = 8
NUM_BINS = 64


def _bin_ids(d: jnp.ndarray) -> jnp.ndarray:
    """bin 0 for INF_RD; else 1 + floor(log2(D)) (D=0 -> bin 1)."""
    dd = jnp.maximum(d, 1.0)
    b = jnp.floor(jnp.log2(dd)).astype(jnp.int32) + 1
    b = jnp.where(d == 0.0, 1, b)
    b = jnp.clip(b, 1, NUM_BINS - 1)
    return jnp.where(d < 0.0, 0, b)


def _contract(rows: jnp.ndarray, onehot: jnp.ndarray) -> jnp.ndarray:
    """[R, TILE] @ [TILE, BINS] on the MXU at full f32 precision.

    At default precision Mosaic feeds the MXU bf16 operands: weights
    and distances lose all but 8 mantissa bits, and the distance-mass
    row came out ~1e-3 off on a TPU v5e.  The one-hot side is exact
    either way.
    """
    return jnp.dot(rows, onehot, precision=jax.lax.Precision.HIGHEST)


def _hist_kernel(d_ref, w_ref, out_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    d = d_ref[...].reshape(-1)        # [TILE]
    w = w_ref[...].reshape(-1)        # [TILE] (0 for padding)
    bins = _bin_ids(d)                # [TILE]
    onehot = (
        bins[:, None] == jax.lax.broadcasted_iota(jnp.int32, (1, NUM_BINS), 1)
    ).astype(jnp.float32)             # [TILE, BINS]
    out_ref[...] += _contract(w[None, :], onehot)  # [1, BINS]


def reuse_hist_pallas_2d(
    d2: jax.Array, w2: jax.Array, *, interpret: bool = False
) -> jax.Array:
    rows, lanes = d2.shape
    assert lanes == LANES and rows % BLOCK_ROWS == 0
    return pl.pallas_call(
        _hist_kernel,
        out_shape=jax.ShapeDtypeStruct((1, NUM_BINS), jnp.float32),
        grid=(rows // BLOCK_ROWS,),
        in_specs=[
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, NUM_BINS), lambda i: (0, 0)),
        interpret=interpret,
    )(d2, w2)


def _moments_kernel(d_ref, w_ref, out_ref):
    """Count + distance-mass histograms in one pass.

    Row 0 of the accumulator is the weighted count per bin (identical
    to :func:`_hist_kernel`); row 1 is the weighted sum of (finite)
    distances per bin, from which the fused profile path derives each
    bin's weighted-mean representative distance without ever reading
    the raw stream back to the host.  Both rows fall out of ONE one-hot
    contraction: a [2, TILE] weight matrix against the [TILE, BINS]
    one-hot — still a single MXU op per tile.
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    d = d_ref[...].reshape(-1)        # [TILE]
    w = w_ref[...].reshape(-1)        # [TILE] (0 for padding)
    bins = _bin_ids(d)                # [TILE]
    onehot = (
        bins[:, None] == jax.lax.broadcasted_iota(jnp.int32, (1, NUM_BINS), 1)
    ).astype(jnp.float32)             # [TILE, BINS]
    wd = w * jnp.maximum(d, 0.0)      # INF sentinel carries no mass
    stacked = jnp.stack([w, wd], axis=0)  # [2, TILE]
    out_ref[...] += _contract(stacked, onehot)  # [2, BINS]


def reuse_hist_moments_pallas_2d(
    d2: jax.Array, w2: jax.Array, *, interpret: bool = False
) -> jax.Array:
    rows, lanes = d2.shape
    assert lanes == LANES and rows % BLOCK_ROWS == 0
    return pl.pallas_call(
        _moments_kernel,
        out_shape=jax.ShapeDtypeStruct((2, NUM_BINS), jnp.float32),
        grid=(rows // BLOCK_ROWS,),
        in_specs=[
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((2, NUM_BINS), lambda i: (0, 0)),
        interpret=interpret,
    )(d2, w2)
