"""Pluggable backends for the coded-matmul pipeline.

Every executor turns (A, B, erasure) into the decoded product C through the
same four stages (encode -> worker products -> erase -> decode); what varies
is WHERE and HOW the worker products are computed:

  reference  pure-jnp einsum oracle (ground truth, any backend, complex ok)
  staged     Pallas encode kernel -> HBM -> Pallas block matmul per worker
  fused      one Pallas megakernel per call; coded tiles live only in VMEM
  mesh       shard_map over a worker axis: one device per worker, A and B
             row-sharded and all-gathered in the program, erasure (binary
             or per-chunk partial) as a runtime mask, all-gather +
             replicated decode

Executors expose ``make_pipeline(plan, kind, dtype)`` returning a pure
function the ``CodedMatmul`` facade jit-compiles and memoises:

  kind == "concrete":  fn(A, B, mask, W)  with W the (mn, K) decode panel
  kind == "traced":    fn(A, B, mask)     in-body masked solve

Partial-straggler kinds are tuples carrying the sub-task count Q
(``runtime/partial.py``): each worker's output rows split into Q cyclic
chunks and decode consumes whatever prefix each worker finished:

  kind == ("partial", Q):         fn(A, B, chunk_masks, W_stack)
                                  chunk_masks (Q, K), W_stack (Q, mn, K)
  kind == ("partial-traced", Q):  fn(A, B, progress)  with progress (K,)

All signatures take the erasure/progress pattern strictly as DATA, so one
compiled executable serves every pattern of that kind.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.core.api import (
    CodedMatmulPlan,
    _coeff_dtype,
    fused_worker_products,
    worker_products,
)
from repro.core.decoding import (
    apply_weights,
    decode_masked,
    decode_with_weights,
    digit_extract,
)
from repro.core.numerics import precise_matmul_t
from repro.core.partition import block_decompose, block_recompose, unpad
from repro.runtime.partial import chunk_bounds
from repro.distributed.sharding import shard_map_compat
from repro.kernels import ops as kops

__all__ = [
    "Executor",
    "LocalExecutor",
    "ReferenceExecutor",
    "StagedKernelExecutor",
    "FusedKernelExecutor",
    "MeshExecutor",
    "resolve_executor",
    "BACKENDS",
    "local_backend_names",
]


@runtime_checkable
class Executor(Protocol):
    """Backend protocol: a name plus a pipeline builder per erasure kind."""

    name: str
    supports_batching: bool

    def make_pipeline(
        self, plan: CodedMatmulPlan, kind: str, dtype
    ) -> Callable:  # pragma: no cover - protocol
        """A pure (A, B, mask[, W]) -> C pipeline for one erasure kind."""
        ...

    def cache_token(self):  # pragma: no cover - protocol
        """Hashable identity for the executable memo: name + any config
        that changes the compiled pipeline (mesh, axis, kernel flags)."""
        ...

    def place_operands(self, A, B):  # pragma: no cover - protocol
        """(A, B) in the layout the pipeline takes them in."""
        ...


class LocalExecutor:
    """Shared single-host pipeline; subclasses provide the worker stage."""

    name = "local"
    supports_batching = True

    def cache_token(self):
        """Executable-memo identity (the name: local executors are config-free)."""
        return self.name

    def place_operands(self, A, B):
        """(A, B) as they are: a local pipeline runs where they are."""
        return A, B

    def worker_products(
        self, plan: CodedMatmulPlan, a_blocks: jnp.ndarray, b_blocks: jnp.ndarray
    ) -> jnp.ndarray:
        """(p, m, bv, br), (p, n, bv, bt) -> all-K worker outputs (K, br, bt)."""
        raise NotImplementedError

    def make_pipeline(self, plan: CodedMatmulPlan, kind, dtype) -> Callable:
        """The single-host 4-stage pipeline for one erasure ``kind``."""
        g = plan.scheme.grid

        def products(A, B):
            with obs.stage(obs.ENCODE):
                a_blocks = block_decompose(A.astype(dtype), g.p, g.m)
                b_blocks = block_decompose(B.astype(dtype), g.p, g.n)
            return self.worker_products(plan, a_blocks, b_blocks)  # (K, br, bt)

        def stages(A, B, mask):
            Y = products(A, B)
            # stage 3 ERASE: zero failed workers' outputs (decode weights
            # also annihilate them; the multiply keeps parity with the mesh
            # pipeline where erased devices genuinely emit garbage).
            return _erase(Y, mask)

        def finish(C_blocks, r, t):
            with obs.stage(obs.RECOMPOSE):
                return unpad(block_recompose(C_blocks), (r, t)).astype(dtype)

        if kind == "products":
            # stage 1+2 only (encode + worker products), for split-stage
            # serving: the (K, br, bt) output feeds a ("decode", r, t)
            # executable later, possibly while the NEXT step's products run.
            return products

        if isinstance(kind, tuple):
            if kind[0] in ("decode", "decode-traced"):
                return self._make_decode_pipeline(plan, kind, finish)
            return self._make_partial_pipeline(plan, kind, dtype, products,
                                               finish)

        if kind == "concrete":

            def fn(A, B, mask, W):
                Y = stages(A, B, mask)
                C_blocks = decode_with_weights(plan.scheme, W, Y, plan.s)
                return finish(C_blocks, A.shape[1], B.shape[1])

            return fn

        z_all = jnp.asarray(plan.z_points)

        def fn(A, B, mask):
            Y = stages(A, B, mask)
            with obs.stage(obs.DECODE):
                C_blocks = decode_masked(plan.scheme, z_all, Y,
                                         mask.astype(Y.real.dtype), plan.s)
            return finish(C_blocks, A.shape[1], B.shape[1])

        return fn

    def _make_decode_pipeline(self, plan: CodedMatmulPlan, kind: tuple,
                              finish: Callable) -> Callable:
        """Stage 3+4 only: erase + decode precomputed worker products.

        The kind tuple carries the ORIGINAL unpadded operand trailing dims
        ``(r, t)`` statically — the products array has padded block shape,
        so the slice that strips the padding cannot be recovered from the
        stage input alone.  Signatures mirror the full pipeline's:

          ("decode", r, t):         fn(Y, mask, W)   with W the (mn, K) panel
          ("decode-traced", r, t):  fn(Y, mask)      in-body masked solve
        """
        style, r, t = kind

        if style == "decode":

            def fn(Y, mask, W):
                Ym = _erase(Y, mask)
                C_blocks = decode_with_weights(plan.scheme, W, Ym, plan.s)
                return finish(C_blocks, r, t)

            return fn

        z_all = jnp.asarray(plan.z_points)

        def fn(Y, mask):
            Ym = _erase(Y, mask)
            with obs.stage(obs.DECODE):
                C_blocks = decode_masked(plan.scheme, z_all, Ym,
                                         mask.astype(Y.real.dtype), plan.s)
            return finish(C_blocks, r, t)

        return fn

    def _make_partial_pipeline(self, plan: CodedMatmulPlan, kind: tuple,
                               dtype, products: Callable,
                               finish: Callable) -> Callable:
        """Prefix-aware pipeline: per-chunk erase + decode, kind carries Q.

        The Q row chunks have static bounds (from the padded block row count),
        so the per-chunk loop is a plain Python loop inside one jitted body —
        chunk c erases with its own (K,) availability row and decodes with
        its own (mn, K) panel, then the chunks concatenate back into the
        full C block rows.  ``Q = 1`` reproduces the binary pipeline exactly
        (one chunk, one mask, one panel).
        """
        style, Q = kind

        if style == "partial":

            def fn(A, B, chunk_masks, W_stack):
                Y = products(A, B)                       # (K, br, bt)
                bounds = chunk_bounds(Y.shape[1], Q)
                parts = []
                for c in range(Q):
                    with obs.stage(obs.DECODE):
                        Yc = _erase(Y[:, bounds[c]:bounds[c + 1], :],
                                    chunk_masks[c])
                        W_c = W_stack[c]
                    parts.append(decode_with_weights(
                        plan.scheme, W_c, Yc, plan.s))
                with obs.stage(obs.RECOMPOSE):
                    C_blocks = jnp.concatenate(parts, axis=2)
                return finish(C_blocks, A.shape[1], B.shape[1])

            return fn

        if style != "partial-traced":
            raise ValueError(f"unknown partial pipeline kind {kind!r}")

        z_all = jnp.asarray(plan.z_points)
        k_idx = jnp.arange(plan.K)

        def fn(A, B, progress):
            Y = products(A, B)                           # (K, br, bt)
            bounds = chunk_bounds(Y.shape[1], Q)
            with obs.stage(obs.DECODE):
                counts = jnp.floor(progress * Q + 1e-9)
            parts = []
            for c in range(Q):
                with obs.stage(obs.DECODE):
                    mask_c = ((c - k_idx) % Q < counts).astype(Y.real.dtype)
                    Yc = _erase(Y[:, bounds[c]:bounds[c + 1], :], mask_c)
                parts.append(decode_masked(
                    plan.scheme, z_all, Yc, mask_c, plan.s))
            with obs.stage(obs.RECOMPOSE):
                C_blocks = jnp.concatenate(parts, axis=2)
            return finish(C_blocks, A.shape[1], B.shape[1])

        return fn


class ReferenceExecutor(LocalExecutor):
    """Pure-jnp staged einsums: the oracle every other backend must match."""

    name = "reference"

    def worker_products(self, plan, a_blocks, b_blocks):
        """Encode + per-worker products as plain einsums (the oracle path)."""
        return worker_products(plan, a_blocks, b_blocks)


class StagedKernelExecutor(LocalExecutor):
    """Pallas encode kernel -> HBM -> Pallas block matmul per worker."""

    name = "staged"

    def worker_products(self, plan, a_blocks, b_blocks):
        """Pallas encode into HBM, then one Pallas block matmul per worker."""
        p, m, bv, br = a_blocks.shape
        _, n, _, bt = b_blocks.shape
        ca = jnp.asarray(plan.coeff_a.reshape(plan.K, p * m),
                         dtype=_coeff_dtype(a_blocks, plan))
        cb = jnp.asarray(plan.coeff_b.reshape(plan.K, p * n),
                         dtype=_coeff_dtype(b_blocks, plan))
        with obs.stage(obs.ENCODE):
            a_tilde = kops.encode(ca, a_blocks.reshape(p * m, bv * br))
            b_tilde = kops.encode(cb, b_blocks.reshape(p * n, bv * bt))
            a_tilde = a_tilde.reshape(plan.K, bv, br)
            b_tilde = b_tilde.reshape(plan.K, bv, bt)
        with obs.stage(obs.DOTS):
            return jnp.stack([kops.matmul_t(a_tilde[k], b_tilde[k])
                              for k in range(plan.K)])


class FusedKernelExecutor(LocalExecutor):
    """Fused encode+product megakernel: coded matrices never touch HBM."""

    name = "fused"

    def worker_products(self, plan, a_blocks, b_blocks):
        """One fused encode+product megakernel call for all K workers."""
        return fused_worker_products(plan, a_blocks, b_blocks)


def _erase(Y: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Stage 3: zero the erased workers' rows of Y (K, ...); ``coded.decode``."""
    with obs.stage(obs.DECODE):
        return Y * mask.astype(Y.dtype)[:, None, None]


# ---------------------------------------------------------------------------
# Mesh backend: the pipeline as one shard_map program, one device per worker.
# ---------------------------------------------------------------------------


def _decode_weights_masked(z_all: jnp.ndarray, mask: jnp.ndarray, tau: int,
                           useful: np.ndarray):
    """Useful rows of the masked pseudo-inverse Vandermonde (in-body solve).

    W_useful (mn, K): X_useful = W_useful @ Y_all (erased rows weighted 0).
    Solved from the normal equations G X = V^T D Y with D = diag(mask);
    LU solve, not explicit inversion - for large tau the Vandermonde normal
    equations are ill-conditioned and G^{-1} squares the error."""
    K = z_all.shape[0]
    V = z_all[:, None] ** jnp.arange(tau)[None, :]          # (K, tau)
    Vw = V * mask.astype(V.dtype)[:, None]
    G = V.T @ Vw                                             # (tau, tau)
    W_full = jnp.linalg.solve(G, Vw.T)
    return W_full[useful]                                    # (mn, K)


def _mesh_local_product(a_blocks, b_blocks, coeff_a, coeff_b, k,
                        *, use_kernels, fused):
    """Stages 1+2 on ONE device: encode worker ``k``'s share, multiply.

    a_blocks (p, m, bv, br) / b_blocks (p, n, bv, bt) replicated; returns
    the (br, bt) block product this device contributes to the all-gather.
    The encode is scoped ``coded.encode``, the product ``coded.dots``
    (``coded.slice`` for the int8 split inside it).
    """
    p, m, bv, br = a_blocks.shape
    _, n, _, bt = b_blocks.shape
    with obs.stage(obs.ENCODE):
        ca = jax.lax.dynamic_index_in_dim(coeff_a, k, axis=0)  # (1, p, m)
        cb = jax.lax.dynamic_index_in_dim(coeff_b, k, axis=0)
    if use_kernels and fused:
        # stages 1+2 fused: coded tiles exist only in VMEM.
        with obs.stage(obs.DOTS):
            return kops.fused_worker(
                ca.reshape(1, p * m), cb.reshape(1, p * n),
                a_blocks.reshape(p * m, bv, br),
                b_blocks.reshape(p * n, bv, bt))[0]           # (br, bt)
    if use_kernels:
        with obs.stage(obs.ENCODE):
            a_tilde = kops.encode(
                ca.reshape(1, p * m),
                a_blocks.reshape(p * m, bv * br)).reshape(bv, br)
            b_tilde = kops.encode(
                cb.reshape(1, p * n),
                b_blocks.reshape(p * n, bv * bt)).reshape(bv, bt)
        with obs.stage(obs.DOTS):
            return kops.matmul_t(a_tilde, b_tilde)            # (br, bt)
    with obs.stage(obs.ENCODE):
        a_tilde = apply_weights(ca.reshape(1, p * m),
                                a_blocks.reshape(p * m, bv, br))[0]
        b_tilde = apply_weights(cb.reshape(1, p * n),
                                b_blocks.reshape(p * n, bv, bt))[0]
    return precise_matmul_t(a_tilde, b_tilde)


def _mesh_blocks(A, B, grid, *, axis, sharded):
    """The operands as one device holds them -> all of their blocks,
    (p, m, bv, br) and (p, n, bv, bt).

    ``sharded`` operands hold this device's rows of v, which an all-gather
    over ``axis`` (``coded.allgather``) joins into the whole operand: A's
    first, so that B's exchange can run under A's encode.  The block
    decomposition is ``coded.encode``.
    """
    blocks = []
    for X, cols in ((A, grid.m), (B, grid.n)):
        if sharded:
            with obs.stage(obs.ALLGATHER):
                X = jax.lax.all_gather(X, axis).reshape(-1, X.shape[-1])
        with obs.stage(obs.ENCODE):
            blocks.append(block_decompose(X, grid.p, cols))
    return blocks


def _mesh_worker_body(A, B, mask, coeff_a, coeff_b, zW,
                      *, grid, sharded, tau, s, useful, axis, use_kernels,
                      fused, have_panel):
    """Per-device body.  A (v, r), B (v, t), row shards of them when
    ``sharded`` (``_mesh_blocks``); mask (K,).

    ``zW`` is the decode operand: the ready (mn, K) weight panel when
    ``have_panel`` (no solve below), else the (K,) evaluation points from
    which the masked normal equations are solved in-body (dynamic masks).
    """
    with obs.stage(obs.ENCODE):
        k = jax.lax.axis_index(axis)
    a_blocks, b_blocks = _mesh_blocks(A, B, grid, axis=axis, sharded=sharded)
    p, m, bv, br = a_blocks.shape
    _, n, _, bt = b_blocks.shape
    y_local = _mesh_local_product(a_blocks, b_blocks, coeff_a, coeff_b, k,
                                  use_kernels=use_kernels, fused=fused)

    # stage 3: erasure - zero out "failed" workers' outputs.
    with obs.stage(obs.DECODE):
        y_local = y_local * jax.lax.dynamic_index_in_dim(mask, k, 0,
                                                         keepdims=False)
    # stage 4: all-gather and decode everywhere (each device keeps its C).
    with obs.stage(obs.ALLGATHER):
        Y = jax.lax.all_gather(y_local, axis)                # (K, br, bt)
    with obs.stage(obs.DECODE):
        if have_panel:
            W = zW                                           # (mn, K), ready
        else:
            W = _decode_weights_masked(zW, mask, tau, useful)  # (mn, K)
        X = apply_weights(W, Y)
    return _mesh_extract(X, s, (m, n, br, bt))


def _mesh_extract(X, s, shape):
    """Digit extraction (``s``) or rounding (None) of the decoded X into C
    blocks of ``shape``; scoped ``coded.extract``."""
    with obs.stage(obs.EXTRACT):
        C = digit_extract(X, s) if s is not None else jnp.round(X)
        return C.reshape(shape)


def _mesh_partial_body(A, B, cm, coeff_a, coeff_b, zW,
                       *, Q, grid, sharded, tau, s, useful, axis, use_kernels,
                       fused, have_panel):
    """Per-device partial-straggler body: ONE block product, Q chunk decodes.

    Operands as in ``_mesh_worker_body``.  Each device emits its block
    product once; after the all-gather every
    device decodes chunk-by-chunk.  ``cm`` is the (Q, K) chunk-availability
    matrix and ``zW`` the stacked (Q, mn, K) decode panels when
    ``have_panel`` (concrete progress); for traced progress ``cm`` is the
    (K,) progress vector, ``zW`` the (K,) evaluation points, and chunk c's
    mask + masked normal equations are derived in-body.  The chunk bounds
    are static (from the padded block row count), so the per-chunk loop is
    a plain Python loop inside the one shard_map program — progress stays
    strictly DATA and one executable serves every progress vector.
    """
    with obs.stage(obs.ENCODE):
        k = jax.lax.axis_index(axis)
    a_blocks, b_blocks = _mesh_blocks(A, B, grid, axis=axis, sharded=sharded)
    p, m, bv, br = a_blocks.shape
    _, n, _, bt = b_blocks.shape
    y_local = _mesh_local_product(a_blocks, b_blocks, coeff_a, coeff_b, k,
                                  use_kernels=use_kernels, fused=fused)

    # stage 4: all-gather the UNMASKED products; stage 3 erasure happens
    # per chunk below (a slow worker's finished prefix still contributes).
    with obs.stage(obs.ALLGATHER):
        Y = jax.lax.all_gather(y_local, axis)                # (K, br, bt)
    bounds = chunk_bounds(br, Q)
    with obs.stage(obs.DECODE):
        if not have_panel:
            counts = jnp.floor(cm * Q + 1e-9)                # (K,)
            k_idx = jnp.arange(Y.shape[0])
        parts = []
        for c in range(Q):
            if have_panel:
                mask_c = cm[c]                               # (K,)
                W_c = zW[c]                                  # (mn, K)
            else:
                # worker k runs chunk (k + j) % Q as its j-th sub-task, so
                # it holds chunk c iff ((c - k) mod Q) < its finished count.
                mask_c = ((c - k_idx) % Q < counts).astype(Y.real.dtype)
                W_c = _decode_weights_masked(zW, mask_c, tau, useful)
            Yc = _erase(Y[:, bounds[c]:bounds[c + 1], :], mask_c)
            parts.append(apply_weights(W_c, Yc))
        X = jnp.concatenate(parts, axis=1)                   # (mn, br, bt)
    return _mesh_extract(X, s, (m, n, br, bt))


class MeshExecutor:
    """One worker per device along a mesh axis; erasure is a runtime mask.

    Operand layout.  A (v, r) and B (v, t) enter the program as row shards
    of their contraction axis v over ``axis`` (``P(axis, None)``, at
    position -2 of a batched shape; replicated along any other mesh axis),
    and the program all-gathers the shards over the chips' interconnect
    before the encode.  Where the K workers do not split v evenly, the
    operands enter whole on every device.  The shape alone decides
    (``operand_layout``).  ``place_operands`` puts operands that live off
    the mesh, such as a single-device array, into that layout; operands
    already on the mesh's devices keep theirs.  The worker products'
    all-gather, the decode on every device and the replicated C follow.
    """

    name = "mesh"
    supports_batching = True  # vmap lifts through shard_map

    def __init__(self, mesh, *, axis: str = "model", use_kernels: bool = True,
                 fused: bool = True):
        if mesh is None:
            raise ValueError("MeshExecutor requires a mesh (backend='mesh')")
        self.mesh = mesh
        self.axis = axis
        self.use_kernels = use_kernels
        self.fused = fused

    def cache_token(self):
        """Executable-memo identity: name + mesh + axis + kernel flags."""
        return (self.name, self.mesh, self.axis, self.use_kernels, self.fused)

    def operand_layout(self, v: int) -> str:
        """``"sharded"`` where the workers split the contraction length
        ``v`` evenly, else ``"replicated"``."""
        return "sharded" if v % self.mesh.shape[self.axis] == 0 else "replicated"

    def operand_sharding(self, shape) -> NamedSharding:
        """Where an operand of ``shape`` (*batch, v, cols) enters the program."""
        if self.operand_layout(shape[-2]) == "replicated":
            return NamedSharding(self.mesh, P())
        return NamedSharding(self.mesh,
                             P(*[None] * (len(shape) - 2), self.axis, None))

    def place_operands(self, A, B):
        """(A, B) in the program's layout; counts ``mesh.operands{layout}``.

        Operands that live off the mesh are moved explicitly, so that each
        device receives only its rows; traced operands and arrays already
        on the mesh's devices are left to the program.
        """
        obs.count("mesh.operands", layout=self.operand_layout(A.shape[-2]))
        devices = set(self.mesh.devices.flat)

        def place(X):
            if isinstance(X, jax.core.Tracer) or (
                    isinstance(X, jax.Array) and X.sharding.device_set == devices):
                return X
            return jax.device_put(X, self.operand_sharding(X.shape))

        return place(A), place(B)

    def make_pipeline(self, plan: CodedMatmulPlan, kind, dtype) -> Callable:
        """The shard_map pipeline (one device per worker) for ``kind``.

        Binary kinds ("concrete"/"traced") and partial-straggler kinds
        (("partial", Q) / ("partial-traced", Q)) are supported; partial
        replicates the stacked (Q, mn, K) decode panels (or solves chunk
        masks in-body when traced) so each device decodes chunk-by-chunk
        after a single all-gather — same signatures as the local pipelines.

        Raises:
            NotImplementedError: for split-stage kinds ("products" /
                ("decode", r, t)), whose stages run fused inside one
                shard_map program, leaving no seam to pipeline across.
            ValueError: if the mesh axis size differs from the plan's K,
                the plan uses complex (unit-circle) evaluation points, or
                the tuple kind is not a known partial style.
        """
        is_stage = (kind == "products"
                    or (isinstance(kind, tuple) and kind
                        and kind[0] in ("decode", "decode-traced")))
        if is_stage:
            raise NotImplementedError(
                f"mesh backend does not support split-stage serving (kind "
                f"{kind!r}): encode, worker products, and decode run fused "
                f"inside one shard_map program, so there is no seam to "
                f"pipeline across. Split worker/decode stages are supported "
                f"by the local backends: {local_backend_names()}.")
        if not isinstance(kind, str) and (
                not isinstance(kind, tuple) or len(kind) != 2
                or kind[0] not in ("partial", "partial-traced")):
            raise ValueError(f"unknown mesh pipeline kind {kind!r}")
        K = self.mesh.shape[self.axis]
        if K != plan.K:
            raise ValueError(
                f"plan built for K={plan.K}, mesh axis {self.axis!r} has {K}")
        if plan.is_complex:
            # the legacy mesh path silently cast the complex encode
            # coefficients to real (discarding imaginary parts -> corrupt
            # decode); an explicit error replaces that silent corruption.
            raise ValueError(
                "mesh backend does not support complex (unit-circle) plans; "
                "use chebyshev/equispaced points or a local backend")
        g = plan.scheme.grid
        useful = np.asarray(plan.scheme.useful_z_exp().reshape(-1))
        s = plan.s if plan.scheme.needs_digit_extraction else None
        coeff_a = jnp.asarray(plan.coeff_a, dtype)
        coeff_b = jnp.asarray(plan.coeff_b, dtype)
        is_partial = isinstance(kind, tuple)
        if is_partial:
            style, Q = kind
            body = partial(
                _mesh_partial_body, Q=Q, grid=g, tau=plan.tau, s=s,
                useful=useful, axis=self.axis, use_kernels=self.use_kernels,
                fused=self.fused, have_panel=(style == "partial"))
        else:
            body = partial(
                _mesh_worker_body, grid=g, tau=plan.tau, s=s, useful=useful,
                axis=self.axis, use_kernels=self.use_kernels,
                fused=self.fused, have_panel=(kind == "concrete"))

        def run(A, B, mask, zW):
            spec = self.operand_sharding(A.shape).spec
            mapped = shard_map_compat(
                partial(body, sharded=(spec != P())),
                mesh=self.mesh,
                in_specs=(spec, spec, P(), P(), P(), P()),
                out_specs=P(),
            )
            with obs.stage(obs.ENCODE):
                A = A.astype(dtype)
                B = B.astype(dtype)
            with obs.stage(obs.DECODE):
                mask = mask.astype(dtype)
                zW = zW.astype(dtype)
            C_blocks = mapped(A, B, mask, coeff_a, coeff_b, zW)
            with obs.stage(obs.RECOMPOSE):
                return unpad(block_recompose(C_blocks),
                             (A.shape[1], B.shape[1])).astype(dtype)

        if is_partial and style == "partial":

            def fn(A, B, chunk_masks, W_stack):
                return run(A, B, chunk_masks, W_stack)

            return fn

        if is_partial:
            z_all_pt = jnp.asarray(plan.z_points, dtype)

            def fn(A, B, progress):
                return run(A, B, progress, z_all_pt)

            return fn

        if kind == "concrete":

            def fn(A, B, mask, W):
                return run(A, B, mask, W)

            return fn

        z_all = jnp.asarray(plan.z_points, dtype)

        def fn(A, B, mask):
            return run(A, B, mask, z_all)

        return fn


BACKENDS = {
    "reference": ReferenceExecutor,
    "staged": StagedKernelExecutor,
    "fused": FusedKernelExecutor,
    "mesh": MeshExecutor,
}

# The split-stage (products / decode) seam only exists on local backends;
# computed ONCE from the registry so error messages cannot drift from it.
_LOCAL_BACKEND_NAMES = ", ".join(sorted(
    name for name, cls in BACKENDS.items()
    if isinstance(cls, type) and issubclass(cls, LocalExecutor)))


def local_backend_names() -> str:
    """Comma-joined names of the local (split-stage capable) backends."""
    return _LOCAL_BACKEND_NAMES


def resolve_executor(backend, *, mesh=None, axis: str = "model",
                     use_kernels: bool = True, fused: bool = True) -> Executor:
    """Executor instance from a backend name (or passthrough instance)."""
    if not isinstance(backend, str):
        if not isinstance(backend, Executor):
            raise TypeError(f"not an Executor: {type(backend).__name__}")
        return backend
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown backend {backend!r}; options: {sorted(BACKENDS)}")
    if backend == "mesh":
        return MeshExecutor(mesh, axis=axis, use_kernels=use_kernels,
                            fused=fused)
    return BACKENDS[backend]()
