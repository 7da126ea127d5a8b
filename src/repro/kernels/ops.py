"""Jit'd public wrappers around the Pallas kernels.

Pad-to-tile, backend dispatch (interpret=True off-TPU so the kernel bodies
execute on CPU for tests/benches), and plan-level convenience entry points
used by the distributed runtime.

Two dtype rules hold at this seam.  Complex operands (unit-circle plans)
have no Pallas TPU lowering and run the jnp oracle instead, counted as
``kernel.fallback{op}`` under ``repro.obs``.  float64 has no Pallas TPU
lowering either: compiling a kernel for the chip with f64 operands raises
and names the XLA worker stage (interpret mode off the chip runs f64).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels import ref
from repro.kernels.block_matmul import matmul_t_pallas
from repro.kernels.coded_decode import decode_pallas, decode_partial_pallas
from repro.kernels.coded_encode import encode_pallas
from repro.kernels.coded_fused import fused_worker_pallas

__all__ = ["encode", "decode", "decode_partial", "matmul_t", "fused_worker",
           "on_tpu"]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return not on_tpu()


def _require_kernel_dtype(op: str, *xs) -> None:
    """Raise before a float64 kernel is compiled for the chip."""
    if _interpret() or not any(x.dtype == jnp.float64 for x in xs):
        return
    raise TypeError(
        f"ops.{op}: Pallas TPU kernels have no float64. For exact float64 "
        "run the XLA worker stage (CodedMatmul backend='reference', or "
        "MeshExecutor(mesh, use_kernels=False) on the mesh backend); for "
        "the kernels use dtype=float32.")


def _pow2_tile(cap: int, dim: int) -> int:
    """Clamp a tile size to the next pow2 >= dim (floor 8), capped at cap."""
    return min(cap, int(2 ** np.ceil(np.log2(max(dim, 8)))))


def _pad_last(x: jnp.ndarray, multiple: int) -> jnp.ndarray:
    pad = (-x.shape[-1]) % multiple
    if pad == 0:
        return x
    width = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
    return jnp.pad(x, width)


def encode(coeff: jnp.ndarray, blocks: jnp.ndarray, *, e_blk: int = 2048) -> jnp.ndarray:
    """coeff: (K, P), blocks: (P, E) -> (K, E) coded blocks (flattened)."""
    if jnp.iscomplexobj(coeff):
        # Pallas TPU has no complex support; unit-circle plans use the oracle.
        obs.count("kernel.fallback", op="encode")
        return ref.encode_ref(coeff, blocks)
    _require_kernel_dtype("encode", coeff, blocks)
    E = blocks.shape[-1]
    e_blk = _pow2_tile(e_blk, E)
    bp = _pad_last(blocks, e_blk)
    out = encode_pallas(coeff, bp, e_blk=e_blk, interpret=_interpret())
    return out[:, :E]


def decode(W: jnp.ndarray, Y: jnp.ndarray, s: float, *, extract: bool = True,
           e_blk: int = 2048) -> jnp.ndarray:
    """W: (mn, tau), Y: (tau, E) -> (mn, E) decoded + digit-extracted."""
    if jnp.iscomplexobj(W) or jnp.iscomplexobj(Y):
        obs.count("kernel.fallback", op="decode")
        return ref.decode_ref(W, Y, s)
    _require_kernel_dtype("decode", W, Y)
    E = Y.shape[-1]
    e_blk = _pow2_tile(e_blk, E)
    Yp = _pad_last(Y, e_blk)
    out = decode_pallas(W, Yp, s=float(s), extract=extract, e_blk=e_blk,
                        interpret=_interpret())
    return out[:, :E]


def decode_partial(W_stack: jnp.ndarray, Y: jnp.ndarray, s: float, *,
                   extract: bool = True, e_blk: int = 2048) -> jnp.ndarray:
    """W_stack: (Q, mn, K), Y: (Q, K, Ec) -> (Q, mn, Ec) per-chunk decode.

    The partial-straggler decode stage: chunk q's worker outputs hit chunk
    q's panel, with digit extraction fused.  Complex panels (unit-circle
    plans) fall back to the per-chunk jnp oracle (counted).
    """
    if jnp.iscomplexobj(W_stack) or jnp.iscomplexobj(Y):
        obs.count("kernel.fallback", op="decode_partial")
        return jnp.stack([ref.decode_ref(W_stack[q], Y[q], s)
                          for q in range(W_stack.shape[0])])
    _require_kernel_dtype("decode_partial", W_stack, Y)
    Ec = Y.shape[-1]
    e_blk = _pow2_tile(e_blk, Ec)
    Yp = _pad_last(Y, e_blk)
    out = decode_partial_pallas(W_stack, Yp, s=float(s), extract=extract,
                                e_blk=e_blk, interpret=_interpret())
    return out[:, :, :Ec]


def fused_worker(
    coeff_a: jnp.ndarray,
    coeff_b: jnp.ndarray,
    a_blocks: jnp.ndarray,
    b_blocks: jnp.ndarray,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 256,
    out_dtype=None,
) -> jnp.ndarray:
    """All-K fused encode+product: coeff_a (K, P), coeff_b (K, Q),
    a_blocks (P, v, r), b_blocks (Q, v, t) -> (K, r, t).

    Pads v/r/t to tile multiples; promotes blocks to the coefficient dtype
    (encode semantics).  Complex plans (unit-circle points) fall back to the
    jnp oracle (counted) - Pallas TPU has no complex support.
    """
    if any(jnp.iscomplexobj(x) for x in (coeff_a, coeff_b, a_blocks, b_blocks)):
        obs.count("kernel.fallback", op="fused_worker")
        return ref.fused_worker_ref(coeff_a, coeff_b, a_blocks, b_blocks,
                                    out_dtype)
    _require_kernel_dtype("fused_worker", coeff_a, coeff_b, a_blocks, b_blocks)
    dt = jnp.result_type(coeff_a.dtype, coeff_b.dtype,
                         a_blocks.dtype, b_blocks.dtype)
    ca = coeff_a.astype(dt)
    cb = coeff_b.astype(dt)
    P, v, r = a_blocks.shape
    Q, _, t = b_blocks.shape
    bm_ = _pow2_tile(bm, r)
    bn_ = _pow2_tile(bn, t)
    # Keep the streamed (P, bk, bm) + (Q, bk, bn) tiles under ~4 MiB f32 so
    # the double-buffered pipeline fits VMEM even for fat block grids.
    bk_cap = max(8, int(2 ** np.floor(np.log2(
        max((4 << 20) // (4 * max(P * bm_ + Q * bn_, 1)), 8)))))
    bk_ = min(_pow2_tile(bk, v), bk_cap)
    pad_a = [(0, 0), (0, (-v) % bk_), (0, (-r) % bm_)]
    pad_b = [(0, 0), (0, (-v) % bk_), (0, (-t) % bn_)]
    ap = jnp.pad(a_blocks.astype(dt), pad_a)
    bp = jnp.pad(b_blocks.astype(dt), pad_b)
    out = fused_worker_pallas(ca, cb, ap, bp, bm=bm_, bn=bn_, bk=bk_,
                              out_dtype=out_dtype, interpret=_interpret())
    return out[:, :r, :t]


def matmul_t(A: jnp.ndarray, B: jnp.ndarray, *, bm: int = 128, bn: int = 128,
             bk: int = 512, out_dtype=None) -> jnp.ndarray:
    """A: (v, r), B: (v, t) -> A^T B with MXU tiling; pads to tile multiples."""
    if jnp.iscomplexobj(A) or jnp.iscomplexobj(B):
        obs.count("kernel.fallback", op="matmul_t")
        return ref.matmul_t_ref(A, B, out_dtype)
    _require_kernel_dtype("matmul_t", A, B)
    v, r = A.shape
    _, t = B.shape
    bm_ = _pow2_tile(bm, r)
    bn_ = _pow2_tile(bn, t)
    bk_ = _pow2_tile(bk, v)
    Ap = jnp.pad(A, (((-v) % bk_ and (0, (-v) % bk_)) or (0, 0),
                     ((-r) % bm_ and (0, (-r) % bm_)) or (0, 0)))
    Bp = jnp.pad(B, (((-v) % bk_ and (0, (-v) % bk_)) or (0, 0),
                     ((-t) % bn_ and (0, (-t) % bn_)) or (0, 0)))
    out = matmul_t_pallas(Ap, Bp, bm=bm_, bn=bn_, bk=bk_, out_dtype=out_dtype,
                          interpret=_interpret())
    return out[:r, :t]
