"""The plain reference: C = A^T B of integer operands, exactly.

It imports nothing of the program under test.  Operands with entries that
fit int8 and products whose every sum fits int32 (``check_range``) are
multiplied as int8 with int32 accumulation, which is exact on any backend
that has an integer dot; the chip's MXU is one.  The same function is the
plain product that ``coding_tax`` divides by.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["check_range", "bench_plain", "max_abs_err"]


def check_range(v: int, lo: int, hi: int) -> None:
    """Raise unless entries in [lo, hi] are int8 and every |C| < 2^31."""
    if not -128 <= lo <= hi <= 127:
        raise ValueError(f"entries [{lo}, {hi}] do not fit int8")
    if v * max(abs(lo), abs(hi)) ** 2 >= 2 ** 31:
        raise ValueError(f"v={v} with entries [{lo}, {hi}] can overflow int32")


@jax.jit
def bench_plain(a8: jnp.ndarray, b8: jnp.ndarray) -> jnp.ndarray:
    """a8 (v, r) int8, b8 (v, t) int8 -> a8^T b8 (r, t) int32, exact."""
    return jax.lax.dot_general(a8, b8, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32)


@jax.jit
def max_abs_err(c: jnp.ndarray, ref: jnp.ndarray) -> jnp.ndarray:
    """max |c - ref| in float64, NaN where c holds one.

    Both sides hold integers under 2^31, so every difference is exact."""
    return jnp.max(jnp.abs(c.astype(jnp.float64) - ref.astype(jnp.float64)))
