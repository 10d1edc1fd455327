"""f32 ``log``/``log1p``/``exp`` accurate to ~1 ulp on every backend.

The SDCM term sums need transcendentals accurate to about one ulp: a
hit rate is a sum of ``exp(log C(D,k) + k log p + (D-k) log1p(-p))``
terms, and a systematic error of ``eps`` in the log terms moves the
rate by about ``eps`` relative.  XLA:CPU's ``log``/``exp`` are within
~1 ulp, but on a TPU v5e XLA's f32 ``log`` is off by up to ~1e-4
absolute and its ``exp`` by up to ~5e-6 relative (with a -7e-7 mean
bias), which put the batched grid ~2.5e-5 off the float64 oracle.

These versions use only f32 multiply/add/divide, ``floor`` and bit
casts, which every backend (and Mosaic, inside Pallas kernels) rounds
as IEEE f32: they are within ~1 ulp on the CPU and the TPU alike.  The
algorithms are the classic fdlibm/musl ``logf`` and ``expf`` reductions.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

__all__ = ["exp_f32", "log1p_f32", "log_f32"]

_LN2_HI = 6.9313812256e-01          # ln 2 with 16 trailing zero bits
_LN2_LO = 9.0580006145e-06          # ln 2 - _LN2_HI
_INV_LN2 = 1.4426950216e+00
# minimax coefficients of (log(1+s) - log(1-s)) / s in s^2
_LG1, _LG2, _LG3, _LG4 = 0.66666662693, 0.40000972152, 0.28498786688, 0.24279078841
# minimax coefficients of the expf remainder polynomial
_P1, _P2 = 1.6666625440e-01, -2.7667332906e-03
_SQRT_HALF_BITS = 0x3F3504F3        # bits of sqrt(2)/2
_ONE_BITS = 0x3F800000
_SQRT_HALF, _SQRT_TWO = 0.70710677, 1.4142135


def _log_reduced(f: jnp.ndarray, dk) -> jnp.ndarray:
    """``log(1 + f) + dk * ln 2`` for f in [sqrt(2)/2 - 1, sqrt(2) - 1]."""
    s = f / (2.0 + f)
    z = s * s
    w = z * z
    r = z * (_LG1 + w * _LG3) + w * (_LG2 + w * _LG4)
    hfsq = 0.5 * f * f
    return s * (hfsq + r) + dk * _LN2_LO - hfsq + f + dk * _LN2_HI


def log_f32(x: jnp.ndarray) -> jnp.ndarray:
    """Natural log of a positive normal f32 (no zero/inf/NaN handling)."""
    # split x = 2^k * m with m in [sqrt(2)/2, sqrt(2))
    ix = lax.bitcast_convert_type(x, jnp.int32) + (_ONE_BITS - _SQRT_HALF_BITS)
    k = (ix >> 23) - 0x7F
    m = lax.bitcast_convert_type((ix & 0x007FFFFF) + _SQRT_HALF_BITS,
                                 jnp.float32)
    return _log_reduced(m - 1.0, k.astype(jnp.float32))


def log1p_f32(x: jnp.ndarray) -> jnp.ndarray:
    """``log(1 + x)`` for x > -1, accurate for tiny |x| as well.

    Near zero ``x`` itself is the reduced argument, so ``1 + x`` is
    never rounded (XLA folds the classic ``(1 + x) - 1`` correction
    away, so that trick is not available).
    """
    near = (x > _SQRT_HALF - 1.0) & (x < _SQRT_TWO - 1.0)
    return jnp.where(near, _log_reduced(x, 0.0), log_f32(1.0 + x))


def exp_f32(x: jnp.ndarray) -> jnp.ndarray:
    """``exp(x)`` for x <= 88; flushes to 0 below -87 (no subnormals)."""
    xc = jnp.clip(x, -87.0, 88.0)
    n = jnp.floor(xc * _INV_LN2 + 0.5)
    hi = xc - n * _LN2_HI
    lo = n * _LN2_LO
    r = hi - lo
    rr = r * r
    c = r - rr * (_P1 + rr * _P2)
    y = 1.0 + (r * c / (2.0 - c) - lo + hi)
    scale = lax.bitcast_convert_type((n.astype(jnp.int32) + 127) << 23,
                                     jnp.float32)
    return jnp.where(x < -87.0, 0.0, y * scale)
