"""Pallas TPU kernel: coded-matmul DECODE stage, fused digit extraction.

X_useful = W @ Y  followed IN-REGISTER by the paper's Sec. III-C extraction
(round -> mod s -> sign recenter).  W is the (mn x tau) panel of the inverse
Vandermonde restricted to the useful z-powers - decoding only ever needs
those mn rows, a tau/mn-fold FLOP and VMEM saving over materialising the
full inverse (for BEC tau = mn so it is square; for the tradeoff scheme the
saving is (mnp'+p'-1)/mn).

Fusing the extraction means the large X intermediate never round-trips to
HBM: the stage reads Y once, writes C once - the memory-optimal schedule.
Grid over E (output elements per C block); W resident in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

__all__ = ["decode_pallas", "decode_partial_pallas"]

# Block indices must be int32: under jax_enable_x64 a Python 0 becomes an
# int64 constant, and Mosaic then fails to legalize the index map.
_ZERO = np.int32(0)


def _decode_kernel(w_ref, y_ref, out_ref, *, s: float, extract: bool):
    X = jnp.dot(w_ref[...], y_ref[...], preferred_element_type=out_ref.dtype,
                precision=jax.lax.Precision.HIGHEST)
    R = jnp.round(X)
    if extract:
        C_hat = R - jnp.floor(R / s) * s          # mod s in [0, s)
        C = jnp.where(C_hat <= s / 2, C_hat, C_hat - s)
    else:
        C = R
    out_ref[...] = C


@functools.partial(
    jax.jit, static_argnames=("s", "extract", "e_blk", "interpret"))
def decode_pallas(
    W: jnp.ndarray,
    Y: jnp.ndarray,
    *,
    s: float,
    extract: bool = True,
    e_blk: int = 2048,
    interpret: bool = False,
) -> jnp.ndarray:
    """W: (mn, tau) decode panel, Y: (tau, E) survivor outputs -> (mn, E).

    ``extract=False`` skips digit extraction (baseline polynomial code:
    useful coefficients are C directly, only rounding applies).
    """
    mn, tau = W.shape
    tau2, E = Y.shape
    assert tau == tau2, (W.shape, Y.shape)
    assert E % e_blk == 0, f"E={E} not a multiple of e_blk={e_blk}"
    kern = functools.partial(_decode_kernel, s=s, extract=extract)
    return pl.pallas_call(
        kern,
        grid=(E // e_blk,),
        in_specs=[
            pl.BlockSpec((mn, tau), lambda e: (_ZERO, _ZERO)),  # resident
            pl.BlockSpec((tau, e_blk), lambda e: (_ZERO, e)),   # streamed
        ],
        out_specs=pl.BlockSpec((mn, e_blk), lambda e: (_ZERO, e)),
        out_shape=jax.ShapeDtypeStruct((mn, E), W.dtype),
        interpret=interpret,
    )(W, Y)


def _decode_partial_kernel(w_ref, y_ref, out_ref, *, s: float, extract: bool):
    X = jnp.dot(w_ref[0], y_ref[0], preferred_element_type=out_ref.dtype,
                precision=jax.lax.Precision.HIGHEST)
    R = jnp.round(X)
    if extract:
        C_hat = R - jnp.floor(R / s) * s          # mod s in [0, s)
        C = jnp.where(C_hat <= s / 2, C_hat, C_hat - s)
    else:
        C = R
    out_ref[0] = C


@functools.partial(
    jax.jit, static_argnames=("s", "extract", "e_blk", "interpret"))
def decode_partial_pallas(
    W_stack: jnp.ndarray,
    Y: jnp.ndarray,
    *,
    s: float,
    extract: bool = True,
    e_blk: int = 2048,
    interpret: bool = False,
) -> jnp.ndarray:
    """Per-chunk decode: W_stack (Q, mn, K), Y (Q, K, Ec) -> (Q, mn, Ec).

    The partial-straggler decode applies a DIFFERENT weight panel to each
    output-row chunk (chunk c only uses the workers whose completed prefix
    covers it).  Grid is (Q, Ec // e_blk): each step loads chunk q's panel
    resident in VMEM and streams one e-block of its worker outputs, with
    the Sec. III-C digit extraction fused in-register as in
    :func:`decode_pallas`.  ``Q = 1`` degenerates to the binary kernel.
    """
    Q, mn, K = W_stack.shape
    Q2, K2, Ec = Y.shape
    assert (Q, K) == (Q2, K2), (W_stack.shape, Y.shape)
    assert Ec % e_blk == 0, f"Ec={Ec} not a multiple of e_blk={e_blk}"
    kern = functools.partial(_decode_partial_kernel, s=s, extract=extract)
    return pl.pallas_call(
        kern,
        grid=(Q, Ec // e_blk),
        in_specs=[
            pl.BlockSpec((1, mn, K), lambda q, e: (q, _ZERO, _ZERO)),  # panel
            pl.BlockSpec((1, K, e_blk), lambda q, e: (q, _ZERO, e)),   # streamed
        ],
        out_specs=pl.BlockSpec((1, mn, e_blk), lambda q, e: (q, _ZERO, e)),
        out_shape=jax.ShapeDtypeStruct((Q, mn, Ec), W_stack.dtype),
        interpret=interpret,
    )(W_stack, Y)
