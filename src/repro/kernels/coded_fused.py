"""Pallas TPU megakernel: fused ENCODE + WORKER-PRODUCT stage.

Computes, for every worker k at once,

    Y_k = (sum_P ca[k, P] * A_P)^T @ (sum_Q cb[k, Q] * B_Q)

directly from the raw block tensors A (P, v, r) and B (Q, v, t).  The coded
matrices A~_k, B~_k exist only as (bk, bm)/(bk, bn) tiles in VMEM inside the
(r, t, v) matmul tiling - they never round-trip through HBM.  Versus the
staged encode_pallas -> matmul_t_pallas schedule this saves, per worker,
2*bv*(br + bt) floats of HBM write+read traffic (the full coded operands)
plus one kernel-dispatch boundary, and lets the encode FLOPs (VPU
scalar-broadcast multiply-adds, P*bk*bm per tile) overlap the MXU matmul in
the same pipeline stage.

Grid: (K, r/bm, t/bn, v/bk) with the contraction axis innermost so the
(bm, bn) accumulator stays resident across the k sweep (output revisiting).
The whole (K, P)/(K, Q) coefficient tables live in SMEM (a few hundred
bytes) and are read as scalars at row ``program_id(0)``: a (1, P) block
would break Mosaic's rule that a block's last two dims divide by (8, 128)
or equal the array's.

VMEM budget per grid step (f32 words):
    P*bk*bm  (A block tiles)  +  Q*bk*bn  (B block tiles)
  + bk*(bm + bn)              (coded tiles, transient)
  + bm*bn                     (accumulator scratch)
With the default bm = bn = 128, bk = 256 and P = Q = 8 this is
2*8*256*128 + 256*256 + 128*128 = ~4.4 MiB f32 - inside the ~16 MiB v5e
VMEM with double buffering.  ops.fused_worker shrinks bk automatically when
P or Q is large so the streamed block tiles stay under ~4 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_worker_pallas"]

# Block indices must be int32: under jax_enable_x64 a Python 0 becomes an
# int64 constant, and Mosaic then fails to legalize the index map.
_ZERO = np.int32(0)


def _fused_kernel(ca_ref, cb_ref, a_ref, b_ref, out_ref, acc_ref, *,
                  k_steps: int):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # ENCODE in VMEM: coded tiles a~ (bk, bm), b~ (bk, bn) as coefficient-
    # weighted sums of the P (resp. Q) source-block tiles.  P, Q are static
    # and small, so the loop unrolls into scalar-broadcast multiply-adds on
    # the VPU; coefficients are scalar reads from the SMEM row.
    kw = pl.program_id(0)
    P = a_ref.shape[0]
    Q = b_ref.shape[0]
    a_tilde = ca_ref[kw, 0] * a_ref[0]
    for pp in range(1, P):
        a_tilde += ca_ref[kw, pp] * a_ref[pp]
    b_tilde = cb_ref[kw, 0] * b_ref[0]
    for qq in range(1, Q):
        b_tilde += cb_ref[kw, qq] * b_ref[qq]

    # WORKER product on the MXU at full f32 precision (the default may take
    # a single bf16 pass); accumulate across the v sweep.
    acc_ref[...] += jnp.dot(
        a_tilde.T, b_tilde, preferred_element_type=acc_ref.dtype,
        precision=jax.lax.Precision.HIGHEST)

    @pl.when(pl.program_id(3) == k_steps - 1)
    def _flush():
        out_ref[0] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "out_dtype", "interpret"))
def fused_worker_pallas(
    coeff_a: jnp.ndarray,
    coeff_b: jnp.ndarray,
    a_blocks: jnp.ndarray,
    b_blocks: jnp.ndarray,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 256,
    out_dtype=None,
    interpret: bool = False,
) -> jnp.ndarray:
    """coeff_a: (K, P), coeff_b: (K, Q), a_blocks: (P, v, r),
    b_blocks: (Q, v, t) -> (K, r, t) all worker products, encode fused in.

    Dims must tile evenly (ops.fused_worker pads); dtypes must match.
    bf16 inputs accumulate in f32.
    """
    K, P = coeff_a.shape
    K2, Q = coeff_b.shape
    P2, v, r = a_blocks.shape
    Q2, v2, t = b_blocks.shape
    assert K == K2, (coeff_a.shape, coeff_b.shape)
    assert P == P2 and Q == Q2, (coeff_a.shape, a_blocks.shape,
                                 coeff_b.shape, b_blocks.shape)
    assert v == v2, (a_blocks.shape, b_blocks.shape)
    assert r % bm == 0 and t % bn == 0 and v % bk == 0, (
        a_blocks.shape, b_blocks.shape, (bm, bn, bk))
    out_dtype = out_dtype or a_blocks.dtype
    acc_dtype = (jnp.float32 if a_blocks.dtype in (jnp.bfloat16, jnp.float16)
                 else a_blocks.dtype)
    k_steps = v // bk
    kern = functools.partial(_fused_kernel, k_steps=k_steps)
    return pl.pallas_call(
        kern,
        grid=(K, r // bm, t // bn, k_steps),
        in_specs=[
            pl.BlockSpec((K, P), lambda kw, i, j, k: (_ZERO, _ZERO),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((K, Q), lambda kw, i, j, k: (_ZERO, _ZERO),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((P, bk, bm), lambda kw, i, j, k: (_ZERO, k, i)),
            pl.BlockSpec((Q, bk, bn), lambda kw, i, j, k: (_ZERO, k, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda kw, i, j, k: (kw, i, j)),
        out_shape=jax.ShapeDtypeStruct((K, r, t), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        interpret=interpret,
    )(coeff_a, coeff_b, a_blocks, b_blocks)
