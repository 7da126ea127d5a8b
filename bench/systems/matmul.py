"""The coded matmul as a system under test: one exact ``C = A^T B`` a call.

A cell drives the system its configuration names (``"system"``, ``matmul``
where it names none; ``bench/spec.py``).  Everything that assumes one coded
``A^T B`` lives here and not in the harness: the facade's build, the
operand pairs, the timed call, the plain product ``coding_tax`` divides by,
the split-stage trace and the check that decides ``correct``.  A new cell,
even of a new kind of system, is new files (data, a system module beside
this one, its reference, its readers) and an entry in ``BENCHMARK.json``,
never an edit here or in the harness.

The configuration fixes the deployment (shapes, block grid, scheme, points,
entry range, precision).  The traffic mix names the ``backend`` that serves
it: ``"reference"`` (every worker on one chip) or ``"mesh"`` (one coded
worker per chip).  Every call erases K - tau workers, uniform over all such
patterns.  The entry the window drives is the public
``CodedMatmul.__call__``.

An item of the pool is one operand pair, made on the device from the seed:
float64 for the program, int8 for the reference.  The check is exact: every
entry of a kept C, on every device that holds a copy of it, equals the int8
``A^T B`` with int32 sums of ``bench/reference.py``.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
import math
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference, tracefile

__all__ = ["CONTROL_DTYPE", "LIMITS", "Pair", "System", "bench_operands", "build"]

# Exact: every entry of the decoded C equals A^T B.  max_abs_err is the
# largest |C - A^T B| over a kept output and every copy of it.
LIMITS = {"max_abs_err": 0.0}
CONTROL_DTYPE = "float32"   # the program's own path one precision below float64
STAGE_CALLS = 3
STAGES = {"worker": "jit_bench_worker_stage", "decode": "jit_bench_decode_stage"}


@dataclasses.dataclass
class Pair:
    """One operand pair, as the program gets it and as the reference does."""

    A: object
    B: object
    A8: object
    B8: object


@functools.partial(jax.jit, static_argnames=("v", "r", "t", "lo", "hi"))
def bench_operands(key, i, *, v, r, t, lo, hi):
    """Pair ``i`` of the pool drawn from ``key``: A (v, r) and B (v, t) with
    integer entries in [lo, hi], as float64 and as int8."""
    ka, kb = jax.random.split(jax.random.fold_in(key, i))
    a = jax.random.randint(ka, (v, r), lo, hi + 1, jnp.int32)
    b = jax.random.randint(kb, (v, t), lo, hi + 1, jnp.int32)
    return (a.astype(jnp.float64), b.astype(jnp.float64),
            a.astype(jnp.int8), b.astype(jnp.int8))


@dataclasses.dataclass
class System:
    """A built facade and what the harness asks of a system under test."""

    cm: object            # repro.runtime.CodedMatmul
    config: dict
    K: int
    tau: int
    shape: tuple          # (v, r, t)
    patterns: list        # every erasure pattern the traffic draws from
    dtype: object
    limits = LIMITS       # each number the check compares, and its limit

    @property
    def item_bytes(self) -> int:
        """Device bytes of one pair: A and B in float64 and in int8."""
        v, r, t = self.shape
        return v * (r + t) * 9

    @property
    def kept_bytes(self) -> int:
        """Device bytes of the outputs the window keeps, one a pattern."""
        _, r, t = self.shape
        return len(self.patterns) * r * t * self.dtype.itemsize

    def make_items(self, seq: np.random.SeedSequence, first: int, count: int) -> list:
        """Pairs ``first .. first + count - 1`` of the pool drawn from
        ``seq``, made on the default device.  A pair's entries depend on the
        seed and its index alone."""
        c = self.config
        key = jax.random.wrap_key_data(jnp.asarray(seq.generate_state(2, np.uint32)))
        return [Pair(*jax.block_until_ready(bench_operands(
                    key, i, v=c["v"], r=c["r"], t=c["t"],
                    lo=c["entry_min"], hi=c["entry_max"])))
                for i in range(first, first + count)]

    def prepare(self) -> None:
        """Factor the decode panel of every erasure pattern."""
        for erased in self.patterns:
            mask = np.ones(self.K)
            mask[list(erased)] = 0.0
            self.cm.panel_cache.get(mask)

    def call(self, item: Pair, erased: tuple):
        """The timed entry: the public facade call on one pair."""
        return self.cm(item.A, item.B, erased=list(erased))

    def plain(self, item: Pair):
        """The plain exact product of the same operands, int8 in, int32 sums."""
        return reference.bench_plain(item.A8, item.B8)

    @staticmethod
    def check_inputs(item: Pair) -> tuple:
        """What the check needs of a pair: its int8 operands."""
        return item.A8, item.B8

    def check(self, kept: dict, refs: list) -> list:
        """Per kept output ``(C, pair index)``, max |C - A^T B| over every
        copy of it; inf where C has another shape or holds a NaN."""
        rt = self.shape[1:]
        errs = []
        for C, k in kept.values():
            A8, B8 = refs[k]
            ref = reference.bench_plain(A8, B8)
            worst = math.inf if tuple(C.shape) != tuple(rt) else 0.0
            for shard in C.addressable_shards if worst == 0.0 else ():
                e = float(reference.max_abs_err(
                    shard.data, jax.device_put(ref[shard.index], shard.device)))
                worst = math.inf if math.isnan(e) else max(worst, e)
            errs.append({"max_abs_err": worst})
        return errs

    def trace_stages(self, item: Pair, device_id: int) -> dict | None:
        """Device ns per call of the facade's split stages, each under a jit
        of the harness's naming; None where the backend has no split seam."""
        cm, (_, r, t) = self.cm, self.shape
        erased = list(self.patterns[0])

        def bench_worker_stage(A, B):
            return cm.worker_stage(A, B)

        def bench_decode_stage(Y):
            return cm.decode_stage(Y, (r, t), erased=erased)

        worker, decode = jax.jit(bench_worker_stage), jax.jit(bench_decode_stage)
        try:
            jax.block_until_ready(decode(worker(item.A, item.B)))
        except NotImplementedError:
            return None
        with tempfile.TemporaryDirectory(prefix="bench-stages-") as tmp:
            jax.profiler.start_trace(tmp)
            for _ in range(STAGE_CALLS):
                jax.block_until_ready(decode(jax.block_until_ready(worker(item.A, item.B))))
            jax.profiler.stop_trace()
            trace = tracefile.load_dir(tmp)
        out = {}
        for stage, prefix in STAGES.items():
            ns, runs = tracefile.module_ns(trace, device_id, prefix)
            if runs:
                out[stage] = ns / runs
        return out


def _backend(name: str, devices: list, K: int):
    """A backend name, or a ``MeshExecutor`` with one coded worker per chip
    along ``model`` (a (len(devices) // K, K) (data, model) mesh).

    The mesh takes each worker product with XLA, as ``coded_serve`` does on
    a TPU: the exact path is float64, which Pallas TPU does not have.
    """
    if name != "mesh":
        return name
    from jax.sharding import AxisType, Mesh

    from repro.runtime import MeshExecutor

    if len(devices) % K:
        raise ValueError(f"a mesh of K={K} workers on {len(devices)} chips")
    mesh = Mesh(np.array(devices).reshape(len(devices) // K, K),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    return MeshExecutor(mesh, use_kernels=False)


def build(config: dict, traffic: dict, devices: list, dtype=None) -> System:
    """Plan and facade for ``config`` served as ``traffic`` says.

    ``dtype`` overrides the configuration's precision (the control runs the
    program's own float32 path).  The plan's entry bound is the paper's
    L = v max|a| max|b| + 1 (Sec. III-D) for the configuration's range,
    which has to fit the reference (``reference.check_range``).
    """
    from repro.core import make_plan
    from repro.runtime import CodedMatmul

    v, r, t = config["v"], config["r"], config["t"]
    reference.check_range(v, config["entry_min"], config["entry_max"])
    amax = max(abs(config["entry_min"]), abs(config["entry_max"]))
    plan = make_plan(config["scheme"], config["p"], config["m"], config["n"],
                     K=config["K"], L=v * amax * amax + 1,
                     points=config["points"])
    dt = jnp.dtype(dtype or config["dtype"])
    cm = CodedMatmul(plan, _backend(traffic["backend"], devices, plan.K),
                     dtype=dt)
    patterns = itertools.combinations(range(plan.K), plan.K - plan.tau)
    return System(cm=cm, config=config, K=plan.K, tau=plan.tau, shape=(v, r, t),
                  patterns=list(patterns), dtype=dt)
