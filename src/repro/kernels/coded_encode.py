"""Pallas TPU kernel: coded-matmul ENCODE stage.

Worker k's coded block A~_k = sum_P coeff[k, P] * blocks[P] - a skinny
(K x P) @ (P x E) matmul with tiny K, P and huge E (= block elements).
Arithmetic intensity is ~K flops/byte of streamed block data, i.e. the stage
is HBM-bandwidth-bound: the kernel's job is to stream `blocks` through VMEM
exactly once while keeping the (K x P) coefficient matrix resident.

Tiling: grid over E; per step the (P, E_blk) tile of `blocks` and the whole
(K, P) coefficient panel live in VMEM; the MXU computes (K, P) @ (P, E_blk).
E_blk defaults to 2048 lanes (f32: P=16 -> 128 KiB in + 256 KiB out for
K=32, comfortably inside the ~16 MiB v5e VMEM with double buffering).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

__all__ = ["encode_pallas"]

# Block indices must be int32: under jax_enable_x64 a Python 0 becomes an
# int64 constant, and Mosaic then fails to legalize the index map.
_ZERO = np.int32(0)


def _encode_kernel(coeff_ref, blocks_ref, out_ref):
    # coeff: (K, P) resident; blocks tile: (P, E_blk); out tile: (K, E_blk).
    out_ref[...] = jnp.dot(
        coeff_ref[...], blocks_ref[...],
        preferred_element_type=out_ref.dtype,
        precision=jax.lax.Precision.HIGHEST,
    )


@functools.partial(jax.jit, static_argnames=("e_blk", "interpret"))
def encode_pallas(
    coeff: jnp.ndarray,
    blocks: jnp.ndarray,
    *,
    e_blk: int = 2048,
    interpret: bool = False,
) -> jnp.ndarray:
    """coeff: (K, P), blocks: (P, E) -> (K, E).  E must divide by e_blk
    (wrappers in ops.py pad); dtypes must match."""
    K, P = coeff.shape
    P2, E = blocks.shape
    assert P == P2, (coeff.shape, blocks.shape)
    assert E % e_blk == 0, f"E={E} not a multiple of e_blk={e_blk}"
    grid = (E // e_blk,)
    return pl.pallas_call(
        _encode_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((K, P), lambda e: (_ZERO, _ZERO)),  # resident panel
            pl.BlockSpec((P, e_blk), lambda e: (_ZERO, e)),  # streamed
        ],
        out_specs=pl.BlockSpec((K, e_blk), lambda e: (_ZERO, e)),
        out_shape=jax.ShapeDtypeStruct((K, E), coeff.dtype),
        interpret=interpret,
    )(coeff, blocks)
