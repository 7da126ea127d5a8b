"""Pallas TPU kernel: the WORKER stage - one coded block product A~^T B~.

Classic MXU-tiled matmul with a transposed LHS: C = A^T @ B where
A: (v, r), B: (v, t).  Grid (r/bm, t/bn, v/bk) with the contraction axis
innermost so the (bm, bn) output tile stays resident in VMEM across the k
sweep (output revisiting); a float32 scratch accumulator gives full-precision
accumulation for bf16 inputs.

Tile defaults (128, 128, 512) are MXU-aligned (multiples of 128 on the lane
axis, 8/16 on the sublane axis) and keep VMEM use ~
bk*bm + bk*bn + bm*bn floats ~ 0.6 MiB f32 - small enough for the
double-buffered pipeline to hide HBM latency.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["matmul_t_pallas"]


def _matmul_t_kernel(a_ref, b_ref, out_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # a tile: (bk, bm) - already the transposed orientation; b tile: (bk, bn).
    acc_ref[...] += jnp.dot(
        a_ref[...].T, b_ref[...], preferred_element_type=acc_ref.dtype,
        precision=jax.lax.Precision.HIGHEST)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "out_dtype", "interpret"))
def matmul_t_pallas(
    A: jnp.ndarray,
    B: jnp.ndarray,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    out_dtype=None,
    interpret: bool = False,
) -> jnp.ndarray:
    """A: (v, r), B: (v, t) -> A^T @ B: (r, t).  Dims must tile evenly
    (ops.py pads).  bf16 inputs accumulate in f32."""
    v, r = A.shape
    v2, t = B.shape
    assert v == v2, (A.shape, B.shape)
    assert r % bm == 0 and t % bn == 0 and v % bk == 0, (A.shape, B.shape, (bm, bn, bk))
    out_dtype = out_dtype or A.dtype
    acc_dtype = jnp.float32 if A.dtype in (jnp.bfloat16, jnp.float16) else A.dtype
    k_steps = v // bk
    kern = functools.partial(_matmul_t_kernel, k_steps=k_steps)
    return pl.pallas_call(
        kern,
        grid=(r // bm, t // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bk, bm), lambda i, j, k: (k, i)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, t), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        interpret=interpret,
    )(A, B)
