"""Logical-axis sharding: a minimal flax-free `logical axes -> mesh axes` map.

Model code annotates tensors with LOGICAL axis names ("dp", "sp", "tp",
"fsdp", None); a context-scoped ``AxisRules`` maps those to physical mesh
axes.  Outside any rules context every annotation is a no-op, so the same
model code runs single-device (smoke tests) and on the production mesh
(dry-run / train) unchanged.

Logical names used across the codebase:
  dp    - data parallel (batch dim)                  -> ("pod", "data")
  fsdp  - fully-sharded parameter dim (ZeRO-3)       -> ("pod", "data")
  sp    - sequence parallel (activations at rest)    -> ("model",)
  tp    - tensor parallel (heads / ffn / experts)    -> ("model",)
  ep    - expert parallel                            -> ("model",)
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["AxisRules", "axis_rules", "current_rules", "shard",
           "logical_sharding", "shard_map_compat"]


def shard_map_compat(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checks off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


AxisName = Union[str, None]


class AxisRules:
    """Maps logical axis names to physical mesh axis names (or None)."""

    def __init__(self, mesh: Mesh, table: Dict[str, Union[str, Tuple[str, ...], None]]):
        self.mesh = mesh
        self.table = dict(table)

    def physical(self, logical: AxisName):
        if logical is None:
            return None
        if logical not in self.table:
            raise KeyError(f"unknown logical axis {logical!r}; known: {list(self.table)}")
        return self.table[logical]

    def spec(self, *logical: AxisName) -> P:
        return P(*[self.physical(a) for a in logical])

    def sharding(self, *logical: AxisName) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(*logical))


_local = threading.local()


def current_rules() -> Optional[AxisRules]:
    return getattr(_local, "rules", None)


@contextlib.contextmanager
def axis_rules(rules: Optional[AxisRules]):
    prev = current_rules()
    _local.rules = rules
    try:
        yield rules
    finally:
        _local.rules = prev


def default_rules(mesh: Mesh, fsdp: bool = True) -> AxisRules:
    """Standard table for the production meshes.

    Single-pod  (data, model):        dp/fsdp -> data,        sp/tp/ep -> model
    Multi-pod   (pod, data, model):   dp/fsdp -> (pod, data), sp/tp/ep -> model

    ``fsdp=False`` replicates parameters over the data axes (pure TP):
    for small models the per-layer FSDP all-gathers dominate the collective
    roofline term - see EXPERIMENTS.md SecPerf.
    """
    axes = mesh.axis_names
    if "pod" in axes:
        dp: Union[str, Tuple[str, ...]] = ("pod", "data")
    else:
        dp = "data"
    return AxisRules(mesh, {
        "dp": dp,
        "fsdp": dp if fsdp else None,
        "sp": "model",
        "tp": "model",
        "ep": "model",
    })


def shard(x, *logical: AxisName):
    """Apply a sharding constraint by logical names; no-op without rules.

    An annotation whose mesh-axis product does not divide the dim size is
    silently dropped (replicated) - this keeps one set of annotations valid
    across architectures (e.g. 14-head attention on a 16-wide tp axis).
    """
    rules = current_rules()
    if rules is None:
        return x
    # Trailing unannotated dims default to replicated.
    names = list(logical) + [None] * (x.ndim - len(logical))
    resolved = []
    for dim, name in zip(x.shape, names[: x.ndim]):
        phys = rules.physical(name) if name is not None else None
        if phys is None:
            resolved.append(None)
            continue
        axes = phys if isinstance(phys, tuple) else (phys,)
        size = 1
        for a in axes:
            size *= rules.mesh.shape[a]
        resolved.append(phys if dim % size == 0 else None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(rules.mesh, P(*resolved)))


def logical_sharding(*logical: AxisName) -> Optional[NamedSharding]:
    """NamedSharding for the current rules (None outside a rules context)."""
    rules = current_rules()
    if rules is None:
        return None
    return rules.sharding(*logical)
