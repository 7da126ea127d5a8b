"""``CodedMatmul``: one executor-agnostic entry point for coded matmuls.

The facade owns everything the three legacy entry points used to own
separately:

* the ``DecodePanelCache`` (host-LU decode weights per erasure pattern);
* erasure normalisation (``erased=`` / ``survivors=`` / 0/1 ``mask``,
  concrete or traced) into one ``ErasurePattern``;
* batching: leading batch dimensions on A and/or B are lifted with vmap;
* a jit-executable memo keyed by (backend, A.shape, B.shape, dtype,
  erasure-kind), so repeated serving calls - including calls with NEW
  erasure patterns of the same kind - reuse one compiled executable,
  named ``coded_<kind>``;
* the call's ``repro.obs`` spans: ``coded.call`` (with the facade's call
  ordinal) around ``coded.panel`` (decode-panel lookup and upload) and
  ``coded.launch`` (the executor's ``place_operands`` and the
  executable's launch).

Usage::

    cm = CodedMatmul(plan)                      # fused Pallas backend
    C  = cm(A, B, erased=[3])                   # or survivors=/mask=
    C2 = cm.with_backend("reference")(A, B)     # same caches, new backend
"""
from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp

import numpy as np

from repro import obs
from repro.core.api import CodedMatmulPlan
from repro.runtime.erasure import ErasurePattern
from repro.runtime.executors import (
    Executor,
    local_backend_names,
    resolve_executor,
)
from repro.runtime.partial import PartialPattern

__all__ = ["CodedMatmul", "CacheGroup", "plan_token"]


def _kind_label(kind) -> str:
    """Bounded-cardinality metric label for an executable kind."""
    return kind if isinstance(kind, str) else str(kind[0])


def _named(fn, kind):
    """``fn`` under the name ``coded_<kind>``, which its jit executable
    takes (``jit_coded_concrete`` on a profile's module line)."""
    def named(*args):
        return fn(*args)

    named.__name__ = "coded_" + _kind_label(kind).replace("-", "_")
    return named


def plan_token(plan: CodedMatmulPlan):
    """Hashable identity of a plan's static configuration.

    Folds in everything a compiled executable or decode panel depends on:
    the scheme (frozen geometry dataclass), worker count, digit base, and
    evaluation points.  Equal-valued plans share a token even when they are
    distinct objects.
    """
    return (plan.scheme, plan.K, plan.s,
            tuple(np.asarray(plan.z_points).ravel().tolist()))


class CacheGroup:
    """Cross-facade shared caches for a FAMILY of plans.

    ``CodedMatmul.with_backend`` already shares caches between sibling
    facades of ONE plan; a ``CacheGroup`` extends that to many plans (the
    control plane's ``PlanLadder`` holds one per ladder).  Executable keys
    fold in each facade's plan token, so distinct rungs never alias a
    compiled program, while the build/hit counters span the whole group —
    ``stats["builds"]`` staying flat across rung switches is the proof that
    switching is recompile-free.  Decode-panel caches remain per-plan
    (panels depend on the scheme and evaluation points) but live here so
    every facade of the same plan shares one.
    """

    def __init__(self):
        self.executables: dict = {}
        self.stats = {"builds": 0, "hits": 0}
        self._panel_caches: dict = {}

    def panel_cache_for(self, plan: CodedMatmulPlan, ridge: float = 0.0):
        """The group's shared ``DecodePanelCache`` for ``plan`` (built once
        per distinct plan token + ridge)."""
        key = (plan_token(plan), ridge)
        pc = self._panel_caches.get(key)
        if pc is None:
            pc = plan.make_panel_cache(ridge)
            self._panel_caches[key] = pc
        return pc

    def seed_extended_panels(self, old_plan: CodedMatmulPlan,
                             new_plan: CodedMatmulPlan,
                             ridge: float = 0.0) -> bool:
        """Seed ``new_plan``'s panel cache from ``old_plan``'s by extension.

        The elastic grow path: when ``new_plan``'s evaluation points
        extend ``old_plan``'s (bit-exact prefix), every decode panel
        cached for the old pool transfers to the grown pool with zero
        columns appended for the new workers
        (``DecodePanelCache.extended``) — no refactorisation, and the old
        plan's cache is untouched.  Returns True when seeding happened;
        False when there was nothing to seed from, the new cache already
        exists, or the points do not extend.
        """
        old = self._panel_caches.get((plan_token(old_plan), ridge))
        new_key = (plan_token(new_plan), ridge)
        if old is None or new_key in self._panel_caches:
            return False
        try:
            self._panel_caches[new_key] = old.extended(
                np.asarray(new_plan.z_points))
        except ValueError:
            return False
        return True

    @property
    def panel_builds(self) -> int:
        """Total decode panels built across every member plan."""
        return sum(pc.builds for pc in self._panel_caches.values())

    def cache_info(self) -> dict:
        """Group-wide executable and decode-panel cache counters."""
        return {
            "builds": self.stats["builds"],
            "hits": self.stats["hits"],
            "entries": len(self.executables),
            "panel_builds": self.panel_builds,
            "plans": len(self._panel_caches),
        }


class CodedMatmul:
    """Coded C = A^T B with a pluggable execution backend.

    A: (*batch, v, r), B: (*batch, v, t) -> C: (*batch, r, t).  Leading
    batch dimensions must match on A and B, or be present on only one of
    them.  The erasure pattern applies to the whole batch (one survivor
    set per serving step).

    Backends: "reference" | "staged" | "fused" (default) | "mesh" (pass
    ``mesh=``, one worker per device along ``axis``).  All backends are
    bit-identical for integer inputs within the plan's bounds.

    On a TPU the kernel backends ("staged", "fused", "mesh" with
    ``use_kernels=True``) take float32 only: Pallas TPU has no float64,
    and a float64 call there raises naming the way out.  Exact float64
    runs on the XLA worker stage: "reference", or "mesh" with
    ``use_kernels=False``.
    """

    def __init__(self, plan: CodedMatmulPlan, backend="fused", *,
                 dtype=jnp.float64, mesh=None, axis: str = "model",
                 use_kernels: bool = True, fused: bool = True,
                 panel_ridge: float = 0.0, cache_group: "CacheGroup" = None,
                 sub_tasks: int = 1, _shared=None):
        if sub_tasks < 1:
            raise ValueError(f"need sub_tasks >= 1, got {sub_tasks}")
        self.sub_tasks = int(sub_tasks)
        self._calls = 0
        self.plan = plan
        self.dtype = jnp.dtype(dtype)
        self._mesh = mesh
        self._axis = axis
        self._use_kernels = use_kernels
        self._fused = fused
        self._plan_token = plan_token(plan)
        self._executor: Executor = resolve_executor(
            backend, mesh=mesh, axis=axis, use_kernels=use_kernels,
            fused=fused)
        if cache_group is not None and _shared is not None:
            raise ValueError("pass cache_group or _shared, not both")
        if cache_group is not None:
            # cross-facade sharing hook: many plans, one executable memo
            # (keys fold in the plan token) + one stats block.
            self.panel_cache = cache_group.panel_cache_for(plan, panel_ridge)
            self._executables = cache_group.executables
            self._stats = cache_group.stats
        elif _shared is not None:
            self.panel_cache, self._executables, self._stats = _shared
        else:
            self.panel_cache = plan.make_panel_cache(panel_ridge)
            self._executables = {}
            self._stats = {"builds": 0, "hits": 0}

    # -- backend plumbing ---------------------------------------------------
    @property
    def backend(self) -> str:
        """Name of the executor serving this facade's calls."""
        return self._executor.name

    def with_backend(self, backend, *, mesh=None, axis: Optional[str] = None,
                     use_kernels: Optional[bool] = None,
                     fused: Optional[bool] = None) -> "CodedMatmul":
        """A sibling facade on another backend, SHARING panel + jit caches."""
        return CodedMatmul(
            self.plan, backend, dtype=self.dtype,
            mesh=self._mesh if mesh is None else mesh,
            axis=self._axis if axis is None else axis,
            use_kernels=self._use_kernels if use_kernels is None else use_kernels,
            fused=self._fused if fused is None else fused,
            sub_tasks=self.sub_tasks,
            _shared=(self.panel_cache, self._executables, self._stats))

    def cache_info(self) -> dict:
        """Executable-memo and panel-cache counters (tests assert on these)."""
        return {
            "builds": self._stats["builds"],
            "hits": self._stats["hits"],
            "entries": len(self._executables),
            "panel_builds": self.panel_cache.builds,
        }

    def executable_cache_size(self) -> int:
        """Total jit-compiled specialisations across memoised executables."""
        total = 0
        for fn in self._executables.values():
            size = getattr(fn, "_cache_size", None)
            total += int(size()) if callable(size) else 1
        return total

    # -- the call -----------------------------------------------------------
    def __call__(self, A, B, erasure: Any = None, *,
                 erased: Optional[Sequence[int]] = None,
                 survivors: Optional[Sequence[int]] = None,
                 mask: Any = None, progress: Any = None,
                 sub_tasks: Optional[int] = None) -> jnp.ndarray:
        """Coded C = A^T B under at most one erasure spec (none = all alive).

        Args:
            A: (*batch, v, r) left operand.
            B: (*batch, v, t) right operand.
            erasure: positional spec — an ``ErasurePattern``, a
                ``PartialPattern``, a (K,) 0/1 mask, or a list of erased
                worker ids.
            erased / survivors / mask: keyword alternatives.
            progress: (K,) fractional progress in [0, 1] — routes through
                the partial-straggler decode (``runtime/partial.py``).
            sub_tasks: per-call override of the facade's sub-task count Q.
                ``Q > 1`` (or an explicit ``progress``/``PartialPattern``)
                selects the partial path; ``Q = 1`` with binary specs is the
                legacy path, bit for bit.

        Returns:
            (*batch, r, t) decoded product.

        Raises:
            ValueError: on conflicting erasure specs, rank-<2 operands,
                contraction mismatch, fewer than tau survivors, or a partial
                progress vector that does not span the decoding system.
        """
        self._calls += 1
        with obs.span(obs.CALL, ordinal=self._calls):
            return self._call(A, B, erasure, erased, survivors, mask,
                              progress, sub_tasks)

    def _call(self, A, B, erasure, erased, survivors, mask, progress,
              sub_tasks) -> jnp.ndarray:
        Q = self.sub_tasks if sub_tasks is None else int(sub_tasks)
        if Q < 1:
            raise ValueError(f"need sub_tasks >= 1, got {Q}")
        if Q > 1 or progress is not None or isinstance(erasure, PartialPattern):
            pattern = PartialPattern.normalize(
                self.plan.K, Q, erasure, progress=progress, erased=erased,
                survivors=survivors, mask=mask)
            return self._call_partial(A, B, pattern)
        pattern = ErasurePattern.normalize(
            self.plan.K, erasure, erased=erased, survivors=survivors,
            mask=mask)
        A = jnp.asarray(A)
        B = jnp.asarray(B)
        self._check_operands(A, B)
        fn = self._get_executable(A, B, pattern.kind)
        args = (A, B, pattern.mask_array(self._mask_dtype()))
        if pattern.kind == "concrete":
            if pattern.n_survivors < self.plan.tau:
                raise ValueError(
                    f"only {pattern.n_survivors} survivors < "
                    f"tau={self.plan.tau}: undecodable")
            with obs.span(obs.PANEL):
                panel = self.panel_cache.get(pattern.mask)
                args += (jnp.asarray(panel.W, self._decode_dtype()),)
        with obs.span(obs.LAUNCH):
            return fn(*self._executor.place_operands(A, B), *args[2:])

    # -- split-stage serving -------------------------------------------------
    def worker_stage(self, A, B) -> jnp.ndarray:
        """Stages 1+2 only: encode + ALL-K worker products (no erase/decode).

        The returned (*batch, K, br, bt) padded block products are what the
        workers hand back before any erasure is applied; feed them to
        :meth:`decode_stage` (with the erasure pattern observed MEANWHILE)
        to finish the step.  Splitting the call lets a serving loop overlap
        decode of step ``t`` with the worker stage of step ``t+1``; the
        composition is bit-identical to the one-shot ``__call__``.

        Raises:
            NotImplementedError: on backends whose pipeline has no
                worker/decode seam (mesh).
        """
        A = jnp.asarray(A)
        B = jnp.asarray(B)
        self._check_operands(A, B)
        fn = self._get_executable(A, B, "products")
        return fn(A, B)

    def decode_stage(self, Y, rt, erasure: Any = None, *,
                     erased: Optional[Sequence[int]] = None,
                     survivors: Optional[Sequence[int]] = None,
                     mask: Any = None, progress: Any = None,
                     sub_tasks: Optional[int] = None) -> jnp.ndarray:
        """Stages 3+4: erase + decode a :meth:`worker_stage` result.

        Args:
            Y: (*batch, K, br, bt) worker products from THIS facade's
                :meth:`worker_stage` (same plan, same operand shapes).
            rt: the original trailing dims ``(r, t)`` =
                ``(A.shape[-1], B.shape[-1])`` — static per executable,
                because slicing the block padding off the recomposed
                product needs concrete sizes the stage input no longer
                carries.
            erasure / erased / survivors / mask: binary erasure spec, as
                for ``__call__`` (concrete or traced).
            progress / sub_tasks: rejected here — partial-straggler specs
                have no split-stage path (see the raise below).

        Returns:
            (*batch, r, t) decoded product, bit-identical to the one-shot
            call under the same pattern.

        Raises:
            ValueError: on conflicting specs or fewer than tau survivors.
            NotImplementedError: on backends with no worker/decode seam,
                and for partial/progress specs: split-stage decode has no
                per-chunk panel path, because the (Q, mn, K) panel stack is
                keyed by the chunk-availability matrix, which the staged
                (K, br, bt) products no longer determine — serve partial
                patterns one-shot via ``cm(A, B, progress=..., sub_tasks=Q)``
                instead (any backend).
        """
        if (progress is not None
                or (sub_tasks is not None and int(sub_tasks) != 1)
                or isinstance(erasure, PartialPattern)):
            raise NotImplementedError(
                "split-stage decode has no per-chunk panel path: "
                "decode_stage accepts only binary erasure specs "
                "(erasure= / erased= / survivors= / mask=). Serve partial "
                "patterns one-shot via cm(A, B, progress=..., sub_tasks=Q) "
                "— supported on every backend, including mesh (the "
                f"worker/decode seam itself exists only on the local "
                f"backends: {local_backend_names()}).")
        Y = jnp.asarray(Y)
        r, t = int(rt[0]), int(rt[1])
        pattern = ErasurePattern.normalize(
            self.plan.K, erasure, erased=erased, survivors=survivors,
            mask=mask)
        kind = (("decode", r, t) if pattern.kind == "concrete"
                else ("decode-traced", r, t))
        fn = self._get_decode_executable(Y, kind)
        mask_arr = pattern.mask_array(self._mask_dtype())
        if pattern.kind == "concrete":
            if pattern.n_survivors < self.plan.tau:
                raise ValueError(
                    f"only {pattern.n_survivors} survivors < "
                    f"tau={self.plan.tau}: undecodable")
            panel = self.panel_cache.get(pattern.mask)
            W = jnp.asarray(panel.W, self._decode_dtype())
            return fn(Y, mask_arr, W)
        return fn(Y, mask_arr)

    def _call_partial(self, A, B, pattern: PartialPattern) -> jnp.ndarray:
        """Partial-straggler decode path: per-chunk masks + panel stack."""
        A = jnp.asarray(A)
        B = jnp.asarray(B)
        self._check_operands(A, B)
        if pattern.is_concrete:
            pattern.require_decodable(self.plan.tau)
            fn = self._get_executable(A, B, ("partial", pattern.Q))
            cm = pattern.chunk_masks
            with obs.span(obs.PANEL):
                W_stack = jnp.asarray(self.panel_cache.get_partial(cm),
                                      self._decode_dtype())
            args = (A, B, jnp.asarray(cm, self._mask_dtype()), W_stack)
        else:
            fn = self._get_executable(A, B, ("partial-traced", pattern.Q))
            args = (A, B, pattern.progress_array(self._mask_dtype()))
        with obs.span(obs.LAUNCH):
            return fn(*self._executor.place_operands(A, B), *args[2:])

    def _check_operands(self, A, B) -> None:
        if A.ndim < 2 or B.ndim < 2:
            raise ValueError(f"need >= 2-D operands, got {A.shape} / {B.shape}")
        if A.shape[-2] != B.shape[-2]:
            raise ValueError(f"contraction mismatch {A.shape} vs {B.shape}")

    # -- executable construction -------------------------------------------
    def _get_executable(self, A, B, kind):
        # the token folds in executor CONFIG (mesh/axis/kernel flags) and
        # the PLAN identity, so with_backend siblings that share a backend
        # name but differ in config — and CacheGroup members on different
        # plans — never alias each other's compiled executables.
        key = (self._plan_token, self._executor.cache_token(), A.shape,
               B.shape, str(self.dtype), kind)
        fn = self._executables.get(key)
        if fn is not None:
            self._stats["hits"] += 1
            obs.count("runtime.executable.hit", kind=_kind_label(kind))
            return fn
        with obs.span("runtime.executable.build", kind=_kind_label(kind), backend=self.backend):
            fn = self._build(A.ndim - 2, B.ndim - 2, kind)
        self._executables[key] = fn
        self._stats["builds"] += 1
        obs.count("runtime.executable.compile", kind=_kind_label(kind))
        return fn

    def _get_decode_executable(self, Y, kind):
        # decode-stage memo: keyed on the PRODUCTS shape plus the static
        # (r, t) folded into the kind — leading dims beyond (K, br, bt)
        # are batch dims, vmapped over Y only (mask/W stay per-step data).
        key = (self._plan_token, self._executor.cache_token(), Y.shape,
               str(self.dtype), kind)
        fn = self._executables.get(key)
        if fn is not None:
            self._stats["hits"] += 1
            obs.count("runtime.executable.hit", kind=_kind_label(kind))
            return fn
        with obs.span("runtime.executable.build", kind=_kind_label(kind), backend=self.backend):
            base = self._executor.make_pipeline(self.plan, kind, self.dtype)
            n_data = 2 if kind[0] == "decode" else 1
            for _ in range(Y.ndim - 3):
                base = jax.vmap(base, in_axes=(0, *([None] * n_data)))
            fn = jax.jit(_named(base, kind))
        self._executables[key] = fn
        self._stats["builds"] += 1
        obs.count("runtime.executable.compile", kind=_kind_label(kind))
        return fn

    def _build(self, a_batch: int, b_batch: int, kind):
        base = self._executor.make_pipeline(self.plan, kind, self.dtype)
        # data operands after (A, B): (mask, W) / (chunk_masks, W_stack) for
        # panel-carrying kinds, (mask,) / (progress,) for traced ones, and
        # none at all for the split worker stage ("products").
        if kind == "products":
            n_data = 0
        else:
            n_data = 2 if kind == "concrete" or (
                isinstance(kind, tuple) and kind[0] == "partial") else 1
        if (a_batch or b_batch) and not self._executor.supports_batching:
            raise NotImplementedError(
                f"backend {self.backend!r} does not support batched operands")
        if a_batch and b_batch and a_batch != b_batch:
            raise ValueError(
                f"batch rank mismatch: A has {a_batch} leading dims, "
                f"B has {b_batch}; batch one operand or both equally")
        fn = base
        for _ in range(max(a_batch, b_batch)):
            in_axes = (0 if a_batch else None, 0 if b_batch else None,
                       *([None] * n_data))
            fn = jax.vmap(fn, in_axes=in_axes)
        return jax.jit(_named(fn, kind))

    # -- dtype policy -------------------------------------------------------
    def _mask_dtype(self):
        return jnp.float64 if self.dtype == jnp.float64 else jnp.float32

    def _decode_dtype(self):
        if self.plan.is_complex:
            return (jnp.complex128 if self.dtype == jnp.float64
                    else jnp.complex64)
        return self.dtype
