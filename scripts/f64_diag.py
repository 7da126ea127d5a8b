"""How exact is float64 on this backend?  A diagnostic for XLA:TPU's f64.

Usage (from the checkout root):
  PYTHONPATH=src python scripts/f64_diag.py ops
  PYTHONPATH=src python scripts/f64_diag.py exact --v 512 2048 8000
  JAX_PLATFORMS=cpu PYTHONPATH=src python scripts/f64_diag.py exact --v 8000

``ops`` runs the float64 operations the exact path is built from on the
default device and compares them with host NumPy (IEEE float64):
elementwise mul, add and divide in units of 2^-52; the residue of large
integers mod s, by ``jnp.mod`` and by ``decoding.digit_extract``; and integer and real dots, by XLA and by
``numerics.sliced_matmul_t``.  On a CPU every error it prints is 0 or a
few units; on a TPU, which emulates float64, they are not.

``exact`` decodes the paper deployment (``configs.paper_matmul.CONFIG``:
entries 0..50, a 2x2x2 grid, K=10 on equispaced points) cut to
v = r = t for each ``--v`` on the default device with the float64
reference backend, and prints the max error against a host NumPy
``A.T @ B`` beside the bits the plan needs (``core.bounds``) and how far
the erasure pattern's decode weights amplify (log2 of the largest row sum
of |W|).  Each kind runs on two patterns: the paper's spread survivors and
four adjacent ones.  The operands are ``chip_smoke.py``'s.  It uses only the public API, so it also
runs against another checkout's ``src``.
"""
from __future__ import annotations

import argparse
import dataclasses
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

# the paper's spread survivors (0, 3, 6, 9), then the clustered (0, 1, 2, 3)
ERASED = {"bec": ([1, 2, 4, 5, 7, 8], [4, 5, 6, 7, 8, 9]),
          "tradeoff": ([1, 2, 4, 5, 7, 8], [4, 5, 6, 7, 8, 9]),
          "polycode": ([0], [9])}


def _ulps(got, want) -> float:
    rel = np.abs(got - want) / np.maximum(np.abs(want), 1e-300)
    return float(np.max(rel) / 2.0 ** -52)


def _run(fn, *xs):
    return np.asarray(jax.jit(fn)(*[jnp.asarray(x) for x in xs]))


def ops_section(n: int = 1 << 20) -> None:
    from repro.core.decoding import digit_extract
    from repro.core.numerics import sliced_matmul_t

    rng = np.random.default_rng(0)
    x, y = rng.uniform(1, 2, n), rng.uniform(1, 2, n)
    for name, fn in (("mul", lambda a, b: a * b), ("add", lambda a, b: a + b),
                     ("div", lambda a, b: a / b)):
        print(f"[ops] {name}: max error {_ulps(_run(fn, x, y), fn(x, y)):.4g} "
              "ulp", flush=True)
    for bits, s in ((44, 2.0 ** 22), (44, 1234567.0), (50, 2.0 ** 26)):
        R = rng.integers(-2 ** bits, 2 ** bits, n).astype(np.float64)
        want = np.mod(R, s)
        want = np.where(want <= s / 2, want, want - s)
        for name, fn in (
                ("jnp.mod", lambda R: (lambda c: jnp.where(
                    c <= s / 2, c, c - s))(jnp.mod(R, s))),
                ("digit_extract", lambda R: digit_extract(R, s))):
            got = _run(fn, R)
            print(f"[ops] residue of |R| < 2^{bits} mod {s:.0f}"
                  f" by {name}: {int(np.sum(got != want))} of {n} wrong, max "
                  f"error {np.max(np.abs(got - want)):.6g}", flush=True)
    k = 4000
    for bits in (16, 20):
        a = rng.integers(0, 2 ** bits, (k, 512)).astype(np.float64)
        b = rng.integers(0, 2 ** bits, (k, 512)).astype(np.float64)
        want = a.T @ b                       # exact: every sum < 2^53
        for name, fn in (("XLA dot", lambda a, b: a.T @ b),
                         ("sliced_matmul_t", sliced_matmul_t)):
            got = _run(fn, a, b)
            print(f"[ops] {bits}-bit integer dot, n={k}, by {name}: max error"
                  f" {np.max(np.abs(got - want)):.6g} (max |C| 2^"
                  f"{np.log2(want.max()):.1f})", flush=True)
    a, b = rng.standard_normal((k, 512)), rng.standard_normal((k, 512))
    want = a.T @ b
    for name, fn in (("XLA dot", lambda a, b: a.T @ b),
                     ("sliced_matmul_t", sliced_matmul_t)):
        got = _run(fn, a, b)
        print(f"[ops] normal dot, n={k}, by {name}: max error / max |C| "
              f"{np.max(np.abs(got - want)) / np.max(np.abs(want)):.4g}",
              flush=True)


def exact_section(vs, kinds, seed: int) -> None:
    from repro.configs.paper_matmul import CONFIG
    from repro.core import make_plan
    from repro.core.bounds import is_safe, max_abs_coefficient
    from repro.core.decoding import make_decode_panel
    from repro.runtime import CodedMatmul

    for v in vs:
        cfg = dataclasses.replace(CONFIG, v=v, r=v, t=v)
        rng = np.random.default_rng(seed)
        A = rng.integers(0, cfg.entry_max + 1, size=(v, v)).astype(np.float64)
        B = rng.integers(0, cfg.entry_max + 1, size=(v, v)).astype(np.float64)
        C_ref = A.T @ B
        for kind in kinds:
            plan = make_plan(kind, cfg.p, cfg.m, cfg.n, K=cfg.K, L=cfg.L,
                             points=cfg.points)
            depth = plan.scheme.digit_depth
            bits = math.log2(max_abs_coefficient(cfg.L, plan.s, depth)
                             * plan.tau)
            safe = is_safe(cfg.L, plan.s, depth, "float64", tau=plan.tau)
            cm = CodedMatmul(plan, "reference", dtype=jnp.float64)
            for erased in ERASED[kind]:
                mask = np.ones(plan.K)
                mask[erased] = 0
                W = make_decode_panel(plan.scheme, plan.z_points, mask).W
                amp = math.log2(np.max(np.sum(np.abs(W), axis=1)))
                t0 = time.perf_counter()
                C = np.asarray(cm(jnp.asarray(A), jnp.asarray(B),
                                  erased=erased))
                err = float(np.max(np.abs(C - C_ref)))
                print(f"[exact] {jax.default_backend()} v={v} {kind} "
                      f"tau={plan.tau} erased={erased}: max error {err!r} "
                      f"({bits:.2f} bits needed, is_safe f64 {safe}, decode "
                      f"weights amplify by 2^{amp:.2f}; "
                      f"{time.perf_counter() - t0:.1f} s)", flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("sections", nargs="+", choices=("ops", "exact"))
    ap.add_argument("--v", type=int, nargs="+", default=[512, 8000])
    ap.add_argument("--kinds", nargs="+", default=["bec"],
                    choices=sorted(ERASED))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    jax.config.update("jax_enable_x64", True)
    print(f"[f64_diag] {jax.devices()[0].platform} "
          f"{jax.devices()[0].device_kind}", flush=True)
    if "ops" in args.sections:
        ops_section()
    if "exact" in args.sections:
        exact_section(args.v, args.kinds, args.seed)


if __name__ == "__main__":
    main()
