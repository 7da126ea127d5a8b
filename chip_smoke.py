"""Smoke test of the coded matmul on a TPU: the main path, end to end.

Run from the root of a checkout, on a machine with a TPU:

    python chip_smoke.py              # one chip: phases 1-4 below
    python chip_smoke.py --chips 4    # four chips: the mesh backend only

One process holds the chip for every phase; a failed phase exits non-zero.
The deployment is the paper's own (``repro.configs.paper_matmul.CONFIG``):
8000 x 8000 integer operands with entries 0..50, a 2x2x2 block grid, K=10
workers on equispaced points.

1. Device: the first device must be a TPU.
2. Kernels at paper widths in float32: the fused megakernel and the staged
   encode + block-matmul kernels compute all K (4000, 4000) worker products
   within 1e-4 of max|Y| of the float64 XLA reference, and the fused
   executable holds a Mosaic kernel (``tpu_custom_call``).
3. Exact in float64 on the XLA worker stage, against a host NumPy
   ``A.T @ B``: every plan that ``core.bounds.is_safe`` calls safe in
   float64 decodes bit-exactly on each erasure pattern run.  At 8000^3 that
   is polycode (tau=9) on three patterns; bec (tau=4, 6 of 10 workers
   erased) has no float64 headroom to spare there, so its max error is
   printed, not asserted.  bec with digit extraction is asserted at the
   deployment's reduced size (``SMOKE``, v=512), where it is safe, on
   spread survivor sets.  ``is_safe`` keeps 4 bits for the decode
   weights' amplification; adjacent survivors amplify by up to 2^7.9 and
   decode inexactly even in IEEE float64 (``scripts/f64_diag.py``).
4. Launcher: ``repro.launch.coded_serve`` serves 3 requests at --size 8000
   on ``--backend reference``; it raises on any inexact request.

``--chips 4`` serves the same 3 requests with ``coded_serve --backend
mesh`` on a (1, 4) mesh, one coded worker per chip (its worker products on
XLA, as float64 needs on a TPU), and compares them with the reference
backend on device 0.

Cold and warm times and peak device bytes are printed as set-up facts.  No
complex plan may fall back from a kernel to the jnp oracle
(``kernel.fallback`` stays 0).  The last line of stdout is one JSON object
naming the device.  The compile cache is ``JAX_COMPILATION_CACHE_DIR`` when
set, else ``.jax_cache`` in the checkout.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import obs  # noqa: E402
from repro.configs.paper_matmul import CONFIG, SMOKE  # noqa: E402
from repro.core import make_plan  # noqa: E402
from repro.core.bounds import is_safe  # noqa: E402
from repro.core.numerics import enable_x64  # noqa: E402
from repro.launch import coded_serve  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.runtime import CodedMatmul  # noqa: E402

KERNEL_REL_TOL = 1e-4
SERVE_ARGS = ["--requests", "3", "--size", "8000"]


def _ms(t0: float) -> float:
    return (time.perf_counter() - t0) * 1e3


def _peak_bytes(devices=None) -> list:
    """peak_bytes_in_use per device (None where the backend reports none)."""
    out = []
    for d in devices or jax.devices()[:1]:
        stats = d.memory_stats() or {}
        out.append(stats.get("peak_bytes_in_use"))
    return out


def operands(cfg, seed: int = 0):
    """Seeded integer A (v, r), B (v, t) with entries 0..entry_max (host)."""
    rng = np.random.default_rng(seed)
    A = rng.integers(0, cfg.entry_max + 1, size=(cfg.v, cfg.r))
    B = rng.integers(0, cfg.entry_max + 1, size=(cfg.v, cfg.t))
    return A.astype(np.float64), B.astype(np.float64)


def _plan(cfg, kind: str):
    return make_plan(kind, cfg.p, cfg.m, cfg.n, K=cfg.K, L=cfg.L,
                     points=cfg.points)


def _timed(fn, *args):
    """(result, cold ms, warm ms): one call that compiles, one that hits."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    cold = _ms(t0)
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, cold, _ms(t0)


@jax.jit
def _rel_err(Y, Y_ref):
    return (jnp.max(jnp.abs(Y.astype(Y_ref.dtype) - Y_ref))
            / jnp.max(jnp.abs(Y_ref)))


def phase_kernels(cfg, A, B) -> str:
    """f32 fused + staged worker products against the f64 reference.

    Returns the compiled text of the fused worker stage."""
    plan = _plan(cfg, "polycode")
    A64, B64 = jnp.asarray(A), jnp.asarray(B)
    Y_ref, cold, warm = _timed(
        CodedMatmul(plan, "reference", dtype=jnp.float64).worker_stage,
        A64, B64)
    print(f"[kernels] reference f64 products {Y_ref.shape}: cold {cold:.1f} "
          f"ms, warm {warm:.1f} ms")
    A32, B32 = A64.astype(jnp.float32), B64.astype(jnp.float32)
    text = ""
    for backend in ("fused", "staged"):
        cm = CodedMatmul(plan, backend, dtype=jnp.float32)
        Y, cold, warm = _timed(cm.worker_stage, A32, B32)
        rel = float(_rel_err(Y, Y_ref))
        print(f"[kernels] {backend} f32 products {Y.shape}: cold {cold:.1f} "
              f"ms, warm {warm:.1f} ms, max|Y - Y_ref| / max|Y_ref| = {rel!r}")
        if not rel <= KERNEL_REL_TOL:
            raise AssertionError(
                f"{backend} products off by {rel!r} > {KERNEL_REL_TOL}")
        if backend == "fused":
            text = jax.jit(cm.worker_stage).lower(A32, B32).compile().as_text()
    print(f"[kernels] peak bytes {_peak_bytes()}")
    return text


EXACT_CASES = (
    (CONFIG, "polycode", ([0], [4], [9])),
    (CONFIG, "bec", ([1, 2, 4, 5, 7, 8],)),
    # spread survivors: 0,3,6,9 and 0,2,7,9 (see the module docstring)
    (SMOKE, "bec", ([1, 2, 4, 5, 7, 8], [1, 3, 4, 5, 6, 8])),
)


def phase_exact() -> None:
    """f64 decodes of ``EXACT_CASES`` against a host NumPy A.T @ B."""
    for cfg, kind, patterns in EXACT_CASES:
        A, B = operands(cfg)
        C_ref = A.T @ B
        A64, B64 = jnp.asarray(A), jnp.asarray(B)
        plan = _plan(cfg, kind)
        safe = is_safe(cfg.L, plan.s, plan.scheme.digit_depth, "float64",
                       tau=plan.tau)
        cm = CodedMatmul(plan, "reference", dtype=jnp.float64)
        for erased in patterns:
            t0 = time.perf_counter()
            C = np.asarray(jax.block_until_ready(cm(A64, B64, erased=erased)))
            ms = _ms(t0)
            err = float(np.max(np.abs(C - C_ref)))
            verdict = "exact" if err == 0.0 else f"max error {err!r}"
            print(f"[exact] {kind} v={cfg.v} tau={plan.tau} erased={erased}: "
                  f"{ms:.1f} ms, {verdict} (is_safe f64: {safe})")
            if safe and err != 0.0:
                raise AssertionError(f"{kind} erased={erased}: {verdict}")
    print(f"[exact] peak bytes {_peak_bytes()}")


def one_chip() -> None:
    with enable_x64():
        text = phase_kernels(CONFIG, *operands(CONFIG))
        if "tpu_custom_call" not in text:
            raise AssertionError("fused executable holds no Mosaic kernel")
        phase_exact()
    t0 = time.perf_counter()
    coded_serve.main(["--backend", "reference", *SERVE_ARGS])
    print(f"[launcher] 3 requests served in {_ms(t0):.1f} ms; peak bytes "
          f"{_peak_bytes()}")


def four_chips() -> None:
    devices = jax.devices()
    if len(devices) != 4:
        raise SystemExit(f"--chips 4 needs 4 devices, have {len(devices)}")
    _, mesh_out = coded_serve.main(["--backend", "mesh", *SERVE_ARGS])
    peaks = _peak_bytes(devices)
    print(f"[mesh] peak bytes per device {peaks}")
    if not all(peaks):
        raise AssertionError(f"a chip of the mesh held nothing: {peaks}")
    _, ref_out = coded_serve.main(["--backend", "reference", *SERVE_ARGS])
    for i, (Cm, Cr) in enumerate(zip(mesh_out, ref_out, strict=True)):
        if not np.array_equal(Cm, Cr):
            raise AssertionError(f"request {i}: mesh != reference")
    print(f"[mesh] {len(mesh_out)} mesh results equal the reference backend "
          f"on {ref_out[0].shape}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: first device is {dev.platform} ({dev.device_kind})",
              file=sys.stderr)
        return 1
    print(f"[device] {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache {enable_compile_cache()}")
    obs.enable(fresh=True)
    (four_chips if args.chips == 4 else one_chip)()
    fallbacks = obs.session().registry.total("kernel.fallback")
    if fallbacks:
        raise AssertionError(f"{fallbacks} kernel calls fell back to jnp")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
