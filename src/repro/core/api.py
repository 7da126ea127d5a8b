"""Plan construction + the encode/product building blocks.

``CodedMatmulPlan`` freezes everything static about one coded matmul;
``worker_products`` / ``fused_worker_products`` are the encode + product
stage primitives the runtime executors are built from.

``coded_matmul`` remains as a deprecation shim over the unified runtime
(``repro.runtime.CodedMatmul``), which owns backend selection, erasure
normalisation, and jit-executable caching.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import bounds as bounds_mod
from repro.core.decoding import DecodePanelCache, apply_weights
from repro.core.numerics import precise_matmul_t
from repro.core.points import make_points
from repro.core.schemes import Scheme, make_scheme

__all__ = ["CodedMatmulPlan", "make_plan", "extend_plan", "shrink_plan",
           "coded_matmul", "worker_products",
           "fused_worker_products", "runtime_facade"]


@dataclasses.dataclass(frozen=True)
class CodedMatmulPlan:
    """Everything static about one coded matmul configuration."""

    scheme: Scheme
    K: int
    s: float
    z_points: np.ndarray          # (K,)
    coeff_a: np.ndarray           # (K, p, m) encode coefficients for A blocks
    coeff_b: np.ndarray           # (K, p, n)

    @property
    def tau(self) -> int:
        return self.scheme.tau

    @property
    def is_complex(self) -> bool:
        return np.iscomplexobj(self.z_points)

    def make_panel_cache(self, ridge: float = 0.0) -> DecodePanelCache:
        """Per-mask decode-panel cache (LU of the masked normal equations).

        Build ONE cache per plan and reuse it across steps: panels are
        factored on the host on first sight of an erasure pattern and
        amortised to a dict lookup afterwards (DESIGN.md Sec. 3.4).
        """
        return DecodePanelCache(self.scheme, self.z_points, ridge)


def make_plan(
    kind: str,
    p: int,
    m: int,
    n: int,
    K: int,
    L: int,
    *,
    p_prime: int = 1,
    points: str = "equispaced",
    s: Optional[float] = None,
    z_points: Optional[np.ndarray] = None,
) -> CodedMatmulPlan:
    """Freeze one coded-matmul configuration into a plan.

    kind:    scheme family - "bec" (Sec. III-B), "tradeoff" (Sec. IV, with
             ``p_prime``), or "polycode" (the Yu et al. baseline).
    p, m, n: block grid - A is split p x m, B is split p x n.
    K:       number of workers (evaluation points); must be >= the scheme's
             recovery threshold tau.
    L:       entry-product bound (Sec. III-D): every C entry and every
             interference product must have magnitude < L.
    points:  evaluation-point family ("equispaced" / "chebyshev" /
             "unit_circle").
    s:       the digit base of the bounded-entry superposition, in the same
             units as the matrix entries (a dimensionless integer scale).
             Default ``None`` picks ``bounds.choose_s(L)`` - the smallest
             power of two >= 2L, which makes digit extraction (round +
             mod s) exact in binary floating point.  An explicit ``s`` must
             be >= 2 (bases below 2 cannot separate digits) and is only
             exact when s >= 2L; it is stored on the plan as ``float``.
    z_points: explicit (K,) evaluation points, overriding ``points``.  The
             elastic paths use this to build plans on a survivor subset or
             a Leja-extended superset of a live pool's points
             (``core.points.extend_points``).
    """
    scheme = make_scheme(kind, p, m, n, p_prime=p_prime)
    if K < scheme.tau:
        raise ValueError(f"K={K} below recovery threshold tau={scheme.tau}")
    if z_points is not None:
        z = np.asarray(z_points)
        if z.shape != (K,):
            raise ValueError(f"z_points shape {z.shape} != ({K},)")
    else:
        z = make_points(points, K)
    s_val = float(s) if s is not None else float(bounds_mod.choose_s(L))
    if s_val < 2:
        raise ValueError(f"digit base s={s_val} must be >= 2 (and >= 2L={2 * L} "
                         "for exact digit extraction)")
    ca, cb = scheme.encode_coeffs(z, s_val)
    return CodedMatmulPlan(scheme=scheme, K=K, s=s_val, z_points=z,
                           coeff_a=ca, coeff_b=cb)


def extend_plan(plan: CodedMatmulPlan, g: int,
                z_new: Optional[np.ndarray] = None) -> CodedMatmulPlan:
    """Grow a plan by ``g`` workers via incremental point extension.

    Evaluation points extend by greedy Leja selection
    (``core.points.extend_points``) and ONLY the ``g`` new coefficient
    rows are computed — the existing rows are reused by reference, so the
    first K rows of the result are bit-identical to ``plan``'s.  Encoding
    is per-point (every scheme's ``encode_coeffs`` evaluates row k from
    ``z_k`` alone), so the same plan is produced by building fresh at
    ``K + g`` with the same points; the incremental path just never
    touches the surviving workers' tasks.

    ``z_new`` optionally supplies the already-extended ``(K + g,)`` point
    set (it must extend ``plan``'s points bit-exactly) so several plans
    sharing one pool extend onto the SAME array.
    """
    if g < 0:
        raise ValueError(f"g must be >= 0, got {g}")
    if g == 0:
        return plan
    from repro.core.points import extend_points

    if z_new is not None:
        z = np.asarray(z_new)
        if z.shape != (plan.K + g,) or not np.array_equal(
                z[:plan.K], np.asarray(plan.z_points)):
            raise ValueError(
                f"z_new must extend the plan's {plan.K} points by {g}")
    else:
        z = extend_points(plan.z_points, g)
    ca_new, cb_new = plan.scheme.encode_coeffs(z[plan.K:], plan.s)
    return CodedMatmulPlan(
        scheme=plan.scheme, K=plan.K + g, s=plan.s, z_points=z,
        coeff_a=np.concatenate([plan.coeff_a, ca_new], axis=0),
        coeff_b=np.concatenate([plan.coeff_b, cb_new], axis=0))


def shrink_plan(plan: CodedMatmulPlan, keep: Sequence[int]) -> CodedMatmulPlan:
    """Shrink a plan to the ``keep`` workers (pool-local indices, in order).

    Survivors keep their evaluation points and coefficient rows (sliced,
    not re-encoded — bit-identical), so their encoded tasks and any decode
    panels for patterns inside the survivor set remain valid.

    Raises:
        ValueError: if ``keep`` has duplicates, indexes outside the pool,
            or leaves fewer than ``tau`` workers (undecodable).
    """
    idx = np.asarray(keep, dtype=np.intp)
    if idx.ndim != 1 or len(set(idx.tolist())) != idx.size:
        raise ValueError(f"keep must be 1-D and duplicate-free, got {keep!r}")
    if idx.size and (idx.min() < 0 or idx.max() >= plan.K):
        raise ValueError(f"keep indexes outside the pool of {plan.K} workers")
    if idx.size < plan.tau:
        raise ValueError(
            f"shrinking to {idx.size} workers breaks tau={plan.tau}")
    return CodedMatmulPlan(
        scheme=plan.scheme, K=int(idx.size), s=plan.s,
        z_points=plan.z_points[idx],
        coeff_a=plan.coeff_a[idx], coeff_b=plan.coeff_b[idx])


def worker_products(plan: CodedMatmulPlan, a_blocks: jnp.ndarray,
                    b_blocks: jnp.ndarray) -> jnp.ndarray:
    """All worker products Y_k = A~_k^T B~_k in plain XLA.

    a_blocks: (p, m, bv, br), b_blocks: (p, n, bv, bt) -> (K, br, bt).
    Worker k encodes its coded pair A~_k, B~_k as a weighted sum of the
    source blocks (``decoding.apply_weights``, elementwise) and multiplies
    them.  The workers run one at a time (``lax.map`` over K), so the only
    dot is the one (bv, br)^T (bv, bt) product live at a time.  That keeps
    the f64 pipeline at the paper's 8000^3 inside one TPU chip's HBM.  The
    product is ``numerics.precise_matmul_t``: on a TPU, where XLA's
    emulated f64 dot falls short of f64, it is a sum of exact int8 slice
    products.  The encode is scoped ``coded.encode``.
    """
    p, m, bv, br = a_blocks.shape
    _, n, _, bt = b_blocks.shape
    ca = jnp.asarray(plan.coeff_a.reshape(plan.K, 1, p * m),
                     dtype=_coeff_dtype(a_blocks, plan))
    cb = jnp.asarray(plan.coeff_b.reshape(plan.K, 1, p * n),
                     dtype=_coeff_dtype(b_blocks, plan))
    with obs.stage(obs.ENCODE):
        a_flat = a_blocks.reshape(p * m, bv, br)
        b_flat = b_blocks.reshape(p * n, bv, bt)

    def one_worker(coeffs):
        ca_k, cb_k = coeffs                       # (1, p*m), (1, p*n)
        with obs.stage(obs.ENCODE):
            a_k = apply_weights(ca_k, a_flat)[0]
            b_k = apply_weights(cb_k, b_flat)[0]
        return precise_matmul_t(a_k, b_k)

    return jax.lax.map(one_worker, (ca, cb))


def fused_worker_products(plan: CodedMatmulPlan, a_blocks: jnp.ndarray,
                          b_blocks: jnp.ndarray) -> jnp.ndarray:
    """All worker products via the fused encode+product Pallas megakernel.

    a_blocks: (p, m, bv, br), b_blocks: (p, n, bv, bt) -> (K, br, bt).
    Equivalent to worker_products but the coded matrices
    A~, B~ are formed only tile-wise in VMEM, never written to HBM.
    """
    from repro.kernels import ops as kops

    p, m, bv, br = a_blocks.shape
    _, n, _, bt = b_blocks.shape
    ca = jnp.asarray(plan.coeff_a.reshape(plan.K, p * m),
                     dtype=_coeff_dtype(a_blocks, plan))
    cb = jnp.asarray(plan.coeff_b.reshape(plan.K, p * n),
                     dtype=_coeff_dtype(b_blocks, plan))
    with obs.stage(obs.DOTS):
        return kops.fused_worker(ca, cb,
                                 a_blocks.reshape(p * m, bv, br),
                                 b_blocks.reshape(p * n, bv, bt))


def _coeff_dtype(x: jnp.ndarray, plan: CodedMatmulPlan):
    if plan.is_complex:
        return jnp.complex128 if x.dtype == jnp.float64 else jnp.complex64
    return x.dtype


# ---------------------------------------------------------------------------
# Legacy entry point: deprecation shim over the unified runtime.
# ---------------------------------------------------------------------------

_RUNTIME_FACADES: dict = {}
_RUNTIME_FACADES_MAX = 64


def runtime_facade(plan: CodedMatmulPlan, backend: str = "fused",
                   dtype=jnp.float64, *, panel_cache=None, **opts):
    """Module-level memo of ``repro.runtime.CodedMatmul`` facades.

    Keyed by plan VALUE (scheme geometry + points + base), not identity, so
    equal plans share one facade - and therefore one decode-panel cache and
    one jit-executable memo - across shim calls.  A caller-supplied
    ``panel_cache`` is part of the key (by identity): callers with their
    own caches get their own facades instead of clobbering the shared one.
    The memo is FIFO-bounded so long-lived processes churning through many
    distinct plans cannot pin executables without limit.
    """
    from repro.runtime import CodedMatmul

    key = (plan.scheme, plan.K, plan.s,
           tuple(np.asarray(plan.z_points).ravel().tolist()),
           str(jnp.dtype(dtype)), backend,
           None if panel_cache is None else id(panel_cache),
           tuple(sorted(opts.items(), key=lambda kv: kv[0])))
    cm = _RUNTIME_FACADES.get(key)
    if cm is None:
        cm = CodedMatmul(plan, backend, dtype=dtype, **opts)
        if panel_cache is not None:
            # facade holds the reference, so id(panel_cache) stays valid
            # for as long as this memo entry lives
            cm.panel_cache = panel_cache
        while len(_RUNTIME_FACADES) >= _RUNTIME_FACADES_MAX:
            _RUNTIME_FACADES.pop(next(iter(_RUNTIME_FACADES)))
        _RUNTIME_FACADES[key] = cm
    return cm


def coded_matmul(
    A: jnp.ndarray,
    B: jnp.ndarray,
    plan: CodedMatmulPlan,
    *,
    erased: Optional[Sequence[int]] = None,
    survivors: Optional[Sequence[int]] = None,
    dtype=jnp.float64,
    fused: bool = False,
) -> jnp.ndarray:
    """DEPRECATED: use ``repro.runtime.CodedMatmul`` instead.

    Compute C = A^T B through the coded pipeline.  A: (v, r), B: (v, t).
    ``erased`` lists worker ids treated as stragglers; alternatively pass an
    explicit ``survivors`` set (decoding now weights ALL listed survivors,
    so order no longer matters).  Exact for integer matrices within the
    plan's numeric bounds.  ``fused=True`` selects the fused megakernel
    backend, ``fused=False`` the staged einsum reference backend.
    """
    warnings.warn(
        "coded_matmul is deprecated; use repro.runtime.CodedMatmul "
        "(plan facade with pluggable backends and jit caching)",
        DeprecationWarning, stacklevel=2)
    if erased is not None and survivors is not None:
        raise ValueError("pass only one of erased/survivors")
    cm = runtime_facade(plan, "fused" if fused else "reference", dtype)
    return cm(A, B, erased=erased, survivors=survivors)


def uncoded_matmul(A: jnp.ndarray, B: jnp.ndarray, dtype=jnp.float64) -> jnp.ndarray:
    """Direct C = A^T B reference; leading batch dims broadcast on either side."""
    return jnp.einsum("...vr,...vt->...rt", A.astype(dtype), B.astype(dtype))
