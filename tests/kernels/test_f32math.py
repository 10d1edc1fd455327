"""``repro.kernels.f32math`` stays within ~1 ulp of float64 over the
ranges the SDCM term sums use, inside Pallas kernels as well."""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode
from repro.kernels.f32math import exp_f32, log1p_f32, log_f32

F32_EPS = float(np.finfo(np.float32).eps)


def _max_rel_err(fn32, fn64, x):
    x = np.asarray(x, np.float32)
    got = np.asarray(jax.jit(fn32)(jnp.asarray(x)), np.float64)
    want = fn64(x.astype(np.float64))
    nz = want != 0
    return float(np.max(np.abs(got[nz] - want[nz]) / np.abs(want[nz])))


@pytest.mark.parametrize("lo,hi", [(1e-30, 1e-3), (1e-3, 0.99),
                                   (0.99, 1.01), (1.01, 3e7)])
def test_log_within_one_ulp(lo, hi):
    x = np.geomspace(lo, hi, 100_001)
    assert _max_rel_err(log_f32, np.log, x) <= F32_EPS


def test_log1p_of_minus_p():
    """``log1p(-p)`` for every A/B of a real cache, including p far
    below f32 epsilon, where ``log(1 - p)`` would read 0."""
    p = np.geomspace(1e-9, 0.999, 100_001)
    assert _max_rel_err(lambda v: log1p_f32(-v), lambda v: np.log1p(-v),
                        p) <= 2 * F32_EPS


@pytest.mark.parametrize("lo,hi", [(-87.0, -20.0), (-20.0, -1.0),
                                   (-1.0, 1.0), (1.0, 88.0)])
def test_exp_within_one_ulp(lo, hi):
    x = np.linspace(lo, hi, 100_001)
    assert _max_rel_err(exp_f32, np.exp, x) <= F32_EPS


def test_exp_flushes_below_the_normal_range():
    out = np.asarray(exp_f32(jnp.asarray([-88.0, -1e4, -jnp.inf])))
    assert np.array_equal(out, np.zeros(3, np.float32))


def test_same_bits_inside_a_pallas_kernel():
    x = jnp.asarray(np.geomspace(1e-3, 1e6, 8 * 128, dtype=np.float32)
                    .reshape(8, 128))

    def kernel(x_ref, o_ref):
        o_ref[...] = exp_f32(-log_f32(x_ref[...]))

    got = pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
        interpret=interpret_mode(),
    )(x)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(jax.jit(
                                      lambda v: exp_f32(-log_f32(v)))(x)))
    # exp(-log x) carries the rounding of log x (|log x| <= 14 here,
    # half an ulp of which is ~4.8e-7) as a relative error
    np.testing.assert_allclose(np.asarray(got), 1.0 / np.asarray(x),
                               rtol=8 * F32_EPS)
