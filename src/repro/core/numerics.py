"""Precision helpers: x64 scoping and exact-integer dtype policy.

The paper's decode requires float64 on the master (Table I uses s up to
2^36, far beyond float32's 24-bit mantissa).  JAX disables x64 by default;
we scope it explicitly so the LM substrate stays f32/bf16 while the coded
matmul reference path runs in f64.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from repro import obs

__all__ = ["enable_x64", "x64_enabled", "precise_matmul_t",
           "sliced_matmul_t"]


@contextlib.contextmanager
def enable_x64(enable: bool = True):
    """Context manager scoping jax_enable_x64 (uses the public config API)."""
    prev = jax.config.read("jax_enable_x64")
    try:
        jax.config.update("jax_enable_x64", enable)
        yield
    finally:
        jax.config.update("jax_enable_x64", prev)


def x64_enabled() -> bool:
    return bool(jax.config.read("jax_enable_x64"))


# float64 worker products on a TPU.  XLA:TPU emulates float64, and its f64
# dot is far short of float64 precision: at the paper's 8000^3 polycode
# decoded with errors in the thousands.  So there the product is taken as
# an Ozaki split: each operand becomes int8 slices of 7 bits under a
# power-of-two scale per output row/column, every slice pair is an exact
# int8 x int8 -> int32 MXU product, and the pairs are summed in float64.
_SLICES = 8                      # 8 x 7 bits: more than f64's 53
_SLICE_SCALE = 128.0             # 2^7
_MAX_CONTRACTION = 2 ** 31 // (_SLICES * 64 * 64)   # int32 cannot overflow


def _emulated_f64() -> bool:
    """Whether XLA on the default backend emulates float64 (a TPU)."""
    return jax.default_backend() == "tpu"


def precise_matmul_t(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a (n, r), b (n, t) -> a^T b at the full precision of their dtype.

    float64 where XLA emulates it takes the int8-slice product
    (``sliced_matmul_t``); everything else is one ``Precision.HIGHEST``
    einsum, scoped ``coded.dots``.
    """
    if a.dtype == jnp.float64 and _emulated_f64():
        return sliced_matmul_t(a, b)
    with obs.stage(obs.DOTS):
        return jnp.einsum("vr,vt->rt", a, b,
                          precision=jax.lax.Precision.HIGHEST)


def _int8_slices(x: jnp.ndarray):
    """x (n, c) float64 -> (slices (S, n, c) int8, exponents (c,) int32).

    x[:, j] == 2^E[j] * sum_l slices[l, :, j] * 128^-(l+1), up to the last
    slice's 2^-56 of the column's largest entry.  Each slice is the rounded
    integer part of the scaled remainder, so |slice| <= 64.  Scoped
    ``coded.slice``.
    """
    with obs.stage(obs.SLICE):
        m = jnp.max(jnp.abs(x), axis=0).astype(jnp.float32)
        _, e = jnp.frexp(m)                       # max |x[:, j]| < 2^e[j]
        E = e + 1                                 # |x / 2^E| < 1/2
        u = x * jnp.ldexp(jnp.float32(1.0), -E).astype(x.dtype)

        def peel(u, _):
            u = u * _SLICE_SCALE                  # exact: a power of two
            q = jnp.round(u.astype(jnp.float32))  # |q| <= 64
            return u - q.astype(x.dtype), q.astype(jnp.int8)   # |u| <= 1/2 + 2^-18

        # a loop, not unrolled: emulated-f64 steps compile slowly on a TPU
        _, slices = jax.lax.scan(peel, u, None, length=_SLICES)
        return slices, E


def sliced_matmul_t(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a^T b from exact int8 slice products, summed in a's dtype.

    a (n, r), b (n, t) -> (r, t).  The slice products are exact in int32;
    pairs (l, m) with equal l + m share a scale and sum exactly in int32
    too, so rounding enters only in the final float sum over l + m.  Pairs
    with l + m > S weigh under 2^-63 of the scales' product and are left
    out.  Every scale stays inside float32's exponent range, which is all
    the TPU's emulated float64 has.  The dots, their scaled sum and the
    final scaling are scoped ``coded.dots``.
    """
    n = a.shape[0]
    if n > _MAX_CONTRACTION:
        raise ValueError(f"contraction {n} > {_MAX_CONTRACTION}: int32 "
                         "slice sums could overflow")
    sa, ea = _int8_slices(a)
    sb, eb = _int8_slices(b)
    with obs.stage(obs.DOTS):
        out = None
        for d in reversed(range(_SLICES + 1)):       # smallest terms first
            # all pairs l + m == d as one dot, contracting over (l, n)
            ls = range(max(0, d - _SLICES + 1), min(d, _SLICES - 1) + 1)
            z = jax.lax.dot_general(
                sa[ls[0]:ls[-1] + 1].reshape(-1, sa.shape[-1]),
                sb[d - ls[-1]:d - ls[0] + 1][::-1].reshape(-1, sb.shape[-1]),
                (((0,), (0,)), ((), ())), preferred_element_type=jnp.int32)
            term = z.astype(a.dtype) * (_SLICE_SCALE ** -d)
            out = term if out is None else out + term
        # 128^-(l+1) * 128^-(m+1): the remaining 2^-14 goes into the scales
        scale_r = jnp.ldexp(jnp.float32(1.0), ea - 7).astype(a.dtype)
        scale_t = jnp.ldexp(jnp.float32(1.0), eb - 7).astype(a.dtype)
        return out * scale_r[:, None] * scale_t[None, :]
