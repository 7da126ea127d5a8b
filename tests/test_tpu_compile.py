"""Compile the main path for a described TPU v5e chip (no chip needed).

The TPU compiler is installed with jaxlib, and it compiles for a chip that
is described by its topology, not attached.  That catches what Pallas
interpret mode never checks: Mosaic's block-shape and dtype rules, VMEM
budgets, and programs that do not fit the chip's HBM.  Nothing runs, so
these tests say nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process may load libtpu at a time, so describing it while modules are
imported would fail every other test worker.  The ``*_pallas`` kernels are
called with ``interpret=False`` directly, because the ``ops`` wrappers see
the CPU backend and would pick interpret mode; for the same reason the
float64 product is told that it runs where XLA emulates float64.
"""
from __future__ import annotations

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs.paper_matmul import CONFIG
from repro.core import make_plan
from repro.core import numerics
from repro.core.numerics import enable_x64
from repro.kernels.block_matmul import matmul_t_pallas
from repro.kernels.coded_encode import encode_pallas
from repro.kernels.coded_fused import fused_worker_pallas
from repro.runtime.executors import ReferenceExecutor

V5E_HBM_BYTES = 15.75e9          # what the compiler lets a v5e program use
E_BLK = 2048                     # ops.encode's streamed tile


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means no topology
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip can be cached but not read back
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was)


def _pad(n: int, tile: int) -> int:
    return -(-n // tile) * tile


def _fused(sds):
    """All-K fused encode+product at paper widths: blocks 4000 -> 4096."""
    g = CONFIG
    P, Q = g.p * g.m, g.p * g.n
    bv, br, bt = (_pad(g.v // g.p, 256), _pad(g.r // g.m, 128),
                  _pad(g.t // g.n, 128))
    fn = jax.jit(lambda ca, cb, a, b: fused_worker_pallas(
        ca, cb, a, b, bm=128, bn=128, bk=256))
    return fn, (sds((g.K, P)), sds((g.K, Q)), sds((P, bv, br)),
                sds((Q, bv, bt)))


def _staged(sds):
    """Encode all K coded A blocks, then one worker's block product."""
    g = CONFIG
    P = g.p * g.m
    E = _pad((g.v // g.p) * (g.r // g.m), E_BLK)
    bv, br, bt = (_pad(g.v // g.p, 512), _pad(g.r // g.m, 128),
                  _pad(g.t // g.n, 128))

    def fn(coeff, blocks, a_tilde, b_tilde):
        return (encode_pallas(coeff, blocks, e_blk=E_BLK),
                matmul_t_pallas(a_tilde, b_tilde))

    return jax.jit(fn), (sds((g.K, P)), sds((P, E)), sds((bv, br)),
                         sds((bv, bt)))


def _reference(kind):
    def build(sds):
        g = CONFIG
        plan = make_plan(kind, g.p, g.m, g.n, K=g.K, L=g.L, points=g.points)
        mn = plan.scheme.useful_z_exp().size
        fn = ReferenceExecutor().make_pipeline(plan, "concrete", jnp.float64)
        return jax.jit(fn), (sds((g.v, g.r)), sds((g.v, g.t)), sds((g.K,)),
                             sds((mn, g.K)))
    return build


CASES = {
    # name: (build, dtype, x64, expect a Mosaic kernel)
    "fused-f32-x64": (_fused, jnp.float32, True, True),
    "fused-f32-x32": (_fused, jnp.float32, False, True),
    "staged-f32-x64": (_staged, jnp.float32, True, True),
    "reference-f64-polycode": (_reference("polycode"), jnp.float64, True,
                               False),
    "reference-f64-bec": (_reference("bec"), jnp.float64, True, False),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_compiles_for_v5e(one_chip, monkeypatch, case):
    build, dtype, x64, kernel = CASES[case]
    monkeypatch.setattr(numerics, "_emulated_f64", lambda: True)
    with enable_x64(x64):
        def sds(shape):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        fn, args = build(sds)
        compiled = fn.lower(*args).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < V5E_HBM_BYTES, (case, total)
    assert ("tpu_custom_call" in compiled.as_text()) == kernel, case
