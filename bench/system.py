"""The system under test, built from a configuration and a traffic mix.

The configuration fixes the deployment (shapes, block grid, scheme, points,
entry range, precision).  The traffic mix names the ``backend`` that serves
it: ``"reference"`` (every worker on one chip) or ``"mesh"`` (one coded
worker per chip).  Every call erases K - tau workers, uniform over all such
patterns.  The entry the window drives is the public
``CodedMatmul.__call__``.
"""
from __future__ import annotations

import dataclasses
import itertools

import numpy as np

__all__ = ["System", "build"]


@dataclasses.dataclass
class System:
    """A built facade and the facts about it that the harness needs."""

    cm: object            # repro.runtime.CodedMatmul
    K: int
    tau: int
    shape: tuple          # (v, r, t)
    patterns: list        # every erasure pattern the traffic draws from
    dtype: object


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
    L = v max|a| max|b| + 1 (Sec. III-D) for the configuration's range.
    """
    import jax.numpy as jnp

    from repro.core import make_plan

    v, r, t = config["v"], config["r"], config["t"]
    amax = max(abs(config["entry_min"]), abs(config["entry_max"]))
    plan = make_plan(config["scheme"], config["p"], config["m"], config["n"],
                     K=config["K"], L=v * amax * amax + 1,
                     points=config["points"])
    from repro.runtime import CodedMatmul

    dt = jnp.dtype(dtype or config["dtype"])
    cm = CodedMatmul(plan, _backend(traffic["backend"], devices, plan.K),
                     dtype=dt)
    patterns = itertools.combinations(range(plan.K), plan.K - plan.tau)
    return System(cm=cm, K=plan.K, tau=plan.tau, shape=(v, r, t),
                  patterns=list(patterns), dtype=dt)
