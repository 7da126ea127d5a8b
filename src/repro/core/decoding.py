"""Decoding: interpolation + digit extraction (paper Sec. III-C).

Given worker outputs Y_k = A~(s,z_k)^T B~(s,z_k) from any tau survivors:

1. Vandermonde-interpolate the z-polynomial coefficients X_0..X_{tau-1}.
2. Select the useful powers X_{phi(i,j)}.
3. Digit extraction (bounded-entry schemes only):
     R   = round(X)            # kills the negative s-digits (< 1/2 total)
     C^  = R mod s             # in [0, s)
     C   = C^            if C^ <= s/2
           C^ - s        otherwise       # sign recentering
   With s a power of two the mod is exact in binary floating point.

For the baseline polynomial code the useful coefficient IS C_ij (round only).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.schemes import Scheme
from repro.core.vandermonde import interpolate_solve, interpolate_masked

__all__ = [
    "digit_extract", "decode", "decode_masked",
    "DecodePanel", "DecodePanelCache", "make_decode_panel",
    "decode_with_panel", "decode_with_weights", "apply_weights",
]


def digit_extract(X: jnp.ndarray, s: float, round_first: bool = True) -> jnp.ndarray:
    """Recover the s^0 digit of X = ... + *s^{-1} + C + *s + ... , |C| < s/2.

    C is round(X) mod s taken in (-s/2, s/2].  The residue is
    R - floor(R / s) * s, the arithmetic of the Pallas decode kernel, so
    the XLA and kernel decodes extract alike.  In IEEE float64 every step
    is exact for an integer |R| < 2^53 and an integer s; on a TPU's
    emulated float64 it agrees with ``jnp.mod`` wherever R is held exactly
    (``scripts/f64_diag.py``).
    """
    R = jnp.round(X) if round_first else X
    C_hat = R - jnp.floor(R / s) * s             # R mod s, in [0, s)
    return jnp.where(C_hat <= s / 2, C_hat, C_hat - s)


def _finish_extract(scheme: Scheme, Xu: jnp.ndarray, s: float,
                    tail: tuple) -> jnp.ndarray:
    """Already-selected useful rows Xu (m*n, ...) -> (m, n, *tail) C blocks:
    real part, digit extraction (or plain rounding), block reshape; scoped
    ``coded.extract``."""
    g = scheme.grid
    with obs.stage(obs.EXTRACT):
        if jnp.iscomplexobj(Xu):
            Xu = Xu.real
        if scheme.needs_digit_extraction:
            C = digit_extract(Xu, s)
        else:
            C = jnp.round(Xu)
        return C.reshape(g.m, g.n, *tail)


def _extract_useful(scheme: Scheme, X: jnp.ndarray, s: float) -> jnp.ndarray:
    """X: (tau, br, bt) coefficients -> (m, n, br, bt) decoded C blocks."""
    idx = scheme.useful_z_exp().reshape(-1)  # (m*n,)
    with obs.stage(obs.DECODE):
        Xu = X[idx]
    return _finish_extract(scheme, Xu, s, X.shape[1:])


def decode(
    scheme: Scheme,
    z_survivors: jnp.ndarray,
    Y_survivors: jnp.ndarray,
    s: float,
) -> jnp.ndarray:
    """Decode from exactly tau survivor outputs (static survivor set).

    z_survivors: (tau,), Y_survivors: (tau, br, bt) -> C blocks (m, n, br, bt).
    """
    tau = scheme.tau
    if z_survivors.shape[0] != tau:
        raise ValueError(
            f"need exactly tau={tau} survivors, got {z_survivors.shape[0]}; "
            "slice the first tau or use decode_masked"
        )
    X = interpolate_solve(jnp.asarray(z_survivors), jnp.asarray(Y_survivors))
    return _extract_useful(scheme, X, s)


def decode_masked(
    scheme: Scheme,
    z_all: jnp.ndarray,
    Y_all: jnp.ndarray,
    mask: jnp.ndarray,
    s: float,
    ridge: float = 0.0,
) -> jnp.ndarray:
    """Decode with a dynamic 0/1 survivor mask over all K workers (jit-safe).

    Requires sum(mask) >= tau; erased rows of Y_all may hold garbage.
    The solve is scoped ``coded.decode``, the extraction ``coded.extract``.
    """
    with obs.stage(obs.DECODE):
        X = interpolate_masked(jnp.asarray(z_all), jnp.asarray(Y_all), mask,
                               scheme.tau, ridge)
    return _extract_useful(scheme, X, s)


# ---------------------------------------------------------------------------
# Decode panels: per-survivor-mask setup factored OUT of the decode hot path.
#
# The masked normal equations G X = V_w^T Y depend only on (z, mask), not on
# the worker outputs Y.  A DecodePanel solves them ONCE on the host (LU
# factorisation of G, then the useful rows of G^{-1} V_w^T) and is reused for
# every subsequent step with the same erasure pattern: decode becomes a
# single (mn, K) @ (K, E) matmul + digit extraction, with no per-call
# factorisation on any device.  Erased workers get zero COLUMNS in W, so
# garbage rows of Y_all are annihilated without touching the mask again.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DecodePanel:
    """Precomputed decode weights for one (z_points, survivor-mask) pair."""

    mask: np.ndarray       # (K,) 0/1 as built
    W: np.ndarray          # (mn, K) useful rows of G^{-1} V_w^T (host const)

    @property
    def K(self) -> int:
        return self.W.shape[1]


def make_decode_panel(scheme: Scheme, z_all: np.ndarray,
                      mask: Optional[np.ndarray] = None,
                      ridge: float = 0.0) -> DecodePanel:
    """Factor the masked normal equations for a CONCRETE survivor mask.

    Pure HOST math (scipy/numpy, never jax): the panel must stay a constant
    even when built inside a trace context, so the jitted/shard-mapped decode
    body that closes over it contains no ``lu``/``triangular_solve``.
    """
    import scipy.linalg as sl

    z = np.asarray(z_all)
    K = z.shape[0]
    # Binarise: panels model 0/1 survivorship (and the cache keys by
    # support), so fractional weights would silently alias a cached panel.
    m = np.ones(K) if mask is None else (np.asarray(mask) != 0).astype(np.float64)
    if m.shape != (K,):
        raise ValueError(f"mask shape {m.shape} != ({K},)")
    if int(np.sum(m != 0)) < scheme.tau:
        raise ValueError(
            f"only {int(np.sum(m != 0))} survivors < tau={scheme.tau}")
    tau = scheme.tau
    V = z[:, None] ** np.arange(tau)[None, :]               # (K, tau)
    Vw = V * m[:, None]
    G = V.conj().T @ Vw                                      # (tau, tau)
    if ridge:
        G = G + ridge * np.eye(tau, dtype=G.dtype)
    lu_piv = sl.lu_factor(G)
    W_full = sl.lu_solve(lu_piv, Vw.conj().T)                # (tau, K)
    useful = np.asarray(scheme.useful_z_exp()).reshape(-1)
    return DecodePanel(mask=m, W=np.asarray(W_full[useful]))


def apply_weights(W: jnp.ndarray, Y: jnp.ndarray) -> jnp.ndarray:
    """X = W @ Y over Y's leading axis: W (mn, K), Y (K, ...) -> (mn, ...).

    The decode contracts over the K workers; the encode uses it too, with
    one row of coefficients over the source blocks.  Written as K unrolled
    scaled adds, one elementwise fusion with no dot: the contraction is
    only K long, so the dot gains nothing, and on the
    TPU an f64 dot is emulated with temporaries many times the size of Y
    (the paper's 8000^3 deployment would not fit one chip's HBM) while an
    f32 dot at default precision takes a single bf16 pass.
    """
    w = W.reshape(W.shape + (1,) * (Y.ndim - 1))
    X = w[:, 0] * Y[0].astype(W.dtype)
    for k in range(1, Y.shape[0]):
        X = X + w[:, k] * Y[k].astype(W.dtype)
    return X


def decode_with_weights(scheme: Scheme, W: jnp.ndarray, Y_all: jnp.ndarray,
                        s: float) -> jnp.ndarray:
    """Decode from a ready (mn, K) weight panel passed as an ARRAY.

    Y_all: (K, br, bt) ALL worker outputs (garbage where erased) ->
    (m, n, br, bt).  No linear solve inside; erased workers have zero
    columns in W.  Because W is an operand (not a closed-over constant),
    one compiled executable serves every concrete erasure pattern.  The
    weighted sums are scoped ``coded.decode``, the extraction
    ``coded.extract``.
    """
    with obs.stage(obs.DECODE):
        Xu = apply_weights(W, Y_all)                         # (mn, br, bt)
    return _finish_extract(scheme, Xu, s, Y_all.shape[1:])


def decode_with_panel(scheme: Scheme, panel: DecodePanel, Y_all: jnp.ndarray,
                      s: float) -> jnp.ndarray:
    """Y_all: (K, br, bt) ALL worker outputs (garbage where erased)
    -> (m, n, br, bt) via the precomputed panel.  No linear solve inside."""
    return decode_with_weights(scheme, jnp.asarray(panel.W), Y_all, s)


class DecodePanelCache:
    """Memoises DecodePanels by erasure pattern.

    The mesh runtime asks for a panel every step; for a stable mask (the
    common case - failures are rare events) this turns decode setup from
    O(tau^3) per call per device into an amortised host-side constant.
    ``builds`` counts actual factorisations (tests assert cache hits).
    """

    def __init__(self, scheme: Scheme, z_all: np.ndarray, ridge: float = 0.0):
        self.scheme = scheme
        self.z_all = np.asarray(z_all)
        self.ridge = ridge
        self.builds = 0
        self._panels: dict = {}
        self._partial_stacks: dict = {}

    def get(self, mask: Optional[np.ndarray] = None) -> DecodePanel:
        K = self.z_all.shape[0]
        m = np.ones(K) if mask is None else np.asarray(mask)
        key = tuple(int(x != 0) for x in m)
        panel = self._panels.get(key)
        if panel is None:
            with obs.span("decode.panel.build"):
                panel = make_decode_panel(self.scheme, self.z_all, m,
                                          self.ridge)
            self._panels[key] = panel
            self.builds += 1
            obs.count("decode.panel_cache.miss", cache="panel")
        else:
            obs.count("decode.panel_cache.hit", cache="panel")
        return panel

    def extended(self, z_new: np.ndarray) -> "DecodePanelCache":
        """A cache over the Leja-extended point set, seeded from this one.

        ``z_new`` must extend this cache's points (``z_new[:K] == z_all``
        bit-exact).  Every cached panel transfers: a K-pool survivor
        pattern is the (K+g)-pool pattern with all new workers erased,
        and masking the new workers zeroes their Vandermonde rows, so the
        normal-equations matrix G — hence the factored weights for the
        old workers — is IDENTICAL, and the new workers contribute zero
        columns.  Seeding therefore pads the cached ``W`` panels with
        zero columns instead of refactoring: growing the pool costs no
        host factorisations for any erasure pattern already seen
        (``builds`` starts at 0; partial stacks transfer the same way).

        Raises:
            ValueError: if ``z_new`` does not extend this cache's points.
        """
        z = np.asarray(z_new)
        K = self.z_all.shape[0]
        if z.ndim != 1 or z.shape[0] < K or not np.array_equal(z[:K],
                                                               self.z_all):
            raise ValueError("z_new must extend this cache's point set "
                             "(bit-exact prefix)")
        g = z.shape[0] - K
        cache = DecodePanelCache(self.scheme, z, self.ridge)
        if g == 0:
            cache._panels = dict(self._panels)
            cache._partial_stacks = dict(self._partial_stacks)
            return cache
        pad_mask = np.zeros(g, dtype=np.float64)
        for key, panel in self._panels.items():
            W = np.concatenate(
                [panel.W, np.zeros((panel.W.shape[0], g), panel.W.dtype)],
                axis=1)
            cache._panels[key + (0,) * g] = DecodePanel(
                mask=np.concatenate([panel.mask, pad_mask]), W=W)
        for key, stack in self._partial_stacks.items():
            new_key = ("partial",) + tuple(row + (0,) * g for row in key[1:])
            cache._partial_stacks[new_key] = np.concatenate(
                [stack, np.zeros(stack.shape[:2] + (g,), stack.dtype)],
                axis=2)
        return cache

    def get_partial(self, chunk_masks: np.ndarray) -> np.ndarray:
        """Stacked (Q, mn, K) decode weights for per-chunk survivor masks.

        ``chunk_masks`` is the (Q, K) 0/1 availability matrix of a concrete
        ``PartialPattern``: row c masks the workers whose completed prefix
        covers output-row chunk c.  Every chunk's panel has the same (mn, K)
        shape, so the stack is a single array operand for the partial decode
        executable.  Per-chunk panels come from :meth:`get`, so chunks
        sharing a survivor set — and binary patterns, where all Q rows are
        identical — share ONE factorisation; the stack itself is memoised by
        the pattern's quantized signature.
        """
        cm = np.asarray(chunk_masks)
        if cm.ndim != 2 or cm.shape[1] != self.z_all.shape[0]:
            raise ValueError(
                f"chunk_masks shape {cm.shape} != (Q, {self.z_all.shape[0]})")
        key = ("partial",) + tuple(
            tuple(int(x != 0) for x in row) for row in cm)
        stack = self._partial_stacks.get(key)
        if stack is None:
            stack = np.stack([self.get(row).W for row in cm])
            self._partial_stacks[key] = stack
            obs.count("decode.panel_cache.miss", cache="stack")
        else:
            obs.count("decode.panel_cache.hit", cache="stack")
        return stack
