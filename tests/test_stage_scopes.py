"""The coded pipeline's stage scopes and host spans.

Scopes are checked in the compiled HLO the CPU runs, in a child process
with four virtual devices (for the mesh) and the TPU's float64 path (the
int8-sliced worker product) switched on.  An op's stage is the innermost
``coded.*`` scope in its ``op_name``.  Ops XLA adds with no ``op_name``
(parameters, tuples, constants) are left out; every other op that runs
(not the body of a fusion or a reducer) must carry a scope, but the loop
control that ``lax.map`` and ``lax.scan`` add around a stage: the trip
counter, its test, the stacking of the loop's outputs.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro import obs

SRC = str(Path(__file__).resolve().parents[1] / "src")

PROGRAMS_CHILD = r"""
import json, re
import jax, jax.numpy as jnp, numpy as np
jax.config.update("jax_enable_x64", True)
from unittest import mock
from jax.sharding import AxisType, Mesh
from repro import obs
from repro.core import make_plan, numerics
from repro.runtime import CodedMatmul, MeshExecutor

OP = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = .*? ([a-z][\w\-]*)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')
SCOPE = re.compile(r"coded\.[a-z]+")
# what lax.map, lax.scan and shard_map add around the stages: the loop, its
# counter and test, the stacking of its outputs, loop state and constants
LOOP = re.compile(r"(^|/)(while/(body|cond)/)?"
                  r"(add|lt|dynamic_update_slice|dynamic_slice|broadcast_in_dim|closed_call"
                  r"|shard_map|broadcast\.\d+)$")
PLUMBING = {"while", "constant", "copy", "get-tuple-element", "tuple", "bitcast"}

def report(text):
    inner = set(re.findall(r"(?:calls|to_apply)=%([\w.\-]+)", text))
    module = re.search(r"^HloModule ([^\s,]+)", text, re.M).group(1)
    scopes, unscoped, loop, top = set(), [], set(), True
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            top = head.group(1) not in inner
            continue
        m = OP.match(line)
        if not m or m.group(2) == "parameter":
            continue
        name = OP_NAME.search(line)
        found = [s for s in SCOPE.findall(name.group(1)) if s in obs.STAGES] if name else []
        scopes.update(found[-1:])
        if top and not found:
            if m.group(2) in PLUMBING:
                loop.add(m.group(2))
            elif name and LOOP.search(name.group(1)):
                loop.add(name.group(1).rsplit("/", 1)[-1])
            elif name:
                unscoped.append(line.strip()[:160])
    return {"module": module, "scopes": sorted(scopes), "unscoped": unscoped,
            "loop": sorted(loop)}

out = {}
with mock.patch.object(numerics, "_emulated_f64", return_value=True):
    a = jnp.ones((64, 32)); b = jnp.ones((64, 16))
    out["sliced_matmul_t"] = report(
        jax.jit(numerics.sliced_matmul_t).lower(a, b).compile().as_text())
    A = jnp.ones((64, 64)); B = jnp.ones((64, 32))
    plan = make_plan("polycode", 2, 2, 2, K=10, L=64 * 50 * 50 + 1)
    cm = CodedMatmul(plan, "reference", dtype=jnp.float64)
    fn = cm._get_executable(A, B, "concrete")
    out["reference"] = report(fn.lower(A, B, jnp.ones(10), jnp.ones((4, 10)))
                              .compile().as_text())
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    plan4 = make_plan("bec", 2, 2, 1, K=4, L=64 * 16 + 1, points="chebyshev")
    cmm = CodedMatmul(plan4, MeshExecutor(mesh, use_kernels=False),
                      dtype=jnp.float64)
    fn = cmm._get_executable(A, B, "concrete")
    out["mesh"] = report(fn.lower(A, B, jnp.ones(4), jnp.ones((2, 4)))
                         .compile().as_text())
print(json.dumps(out))
"""

ALL = sorted(obs.STAGES)


def _child(code: str, devices: int = 1, timeout: int = 600) -> str:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=SRC + os.pathsep + os.environ.get("PYTHONPATH", ""),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout.strip().splitlines()[-1]


@pytest.fixture(scope="module")
def programs():
    return json.loads(_child(PROGRAMS_CHILD, devices=4))


@pytest.mark.parametrize("program,module,scopes", [
    ("sliced_matmul_t", "jit_sliced_matmul_t", ["coded.dots", "coded.slice"]),
    ("reference", "jit_coded_concrete",
     [s for s in ALL if s != "coded.allgather"]),
    # the mesh's C needs no relayout here: its recompose compiles to nothing
    ("mesh", "jit_coded_concrete", [s for s in ALL if s != "coded.recompose"]),
])
def test_every_op_carries_a_stage_scope(programs, program, module, scopes):
    """Each stage the program has appears in its compiled ops' metadata, and
    no op that runs lacks a scope but the loop control around the stages;
    facade executables are named coded_<kind>."""
    got = programs[program]
    assert got["module"] == module
    assert got["scopes"] == scopes
    assert got["unscoped"] == []


def test_stage_rejects_unknown_names():
    """Stage names are defined once, in repro.obs."""
    with pytest.raises(ValueError):
        obs.stage("coded.misc")
    assert obs.stage(obs.DECODE) is not None


PROFILE_CHILD = r"""
import json, sys, tempfile
from pathlib import Path
import jax, jax.numpy as jnp
jax.config.update("jax_enable_x64", True)
from jax.profiler import ProfileData
from repro import obs
from repro.core import make_plan
from repro.runtime import CodedMatmul

plan = make_plan("bec", 2, 2, 1, K=4, L=32 * 16 + 1, points="chebyshev")
cm = CodedMatmul(plan, "reference", dtype=jnp.float64)
A = jnp.ones((32, 16)); B = jnp.ones((32, 8))
clock = sys.argv[1]
obs.enable(fresh=True, clock=obs.SettableClock() if clock == "settable" else obs.MONOTONIC)
cm(A, B, erased=[1]).block_until_ready()
tmp = tempfile.mkdtemp()
jax.profiler.start_trace(tmp)
cm(A, B, erased=[2]).block_until_ready()
jax.profiler.stop_trace()
events = []
for plane in ProfileData.from_file(str(next(Path(tmp).rglob("*.xplane.pb")))).planes:
    for line in plane.lines:
        for e in line.events:
            if e.name.startswith("coded."):
                events.append([e.name, dict((k, str(v)) for k, v in e.stats)])
spans = [s.name for s in obs.session().recorder.spans if s.name.startswith("coded.")]
print(json.dumps({"events": events, "spans": spans}))
"""


@pytest.mark.parametrize("clock,annotated", [("monotonic", True),
                                             ("settable", False)])
def test_facade_spans_reach_the_profile_on_the_real_clock(clock, annotated):
    """A profiled call writes coded.call (with its ordinal), coded.panel and
    coded.launch into the .xplane.pb beside the recorder's spans; spans on a
    simulated clock stay in the recorder."""
    got = json.loads(_child(PROFILE_CHILD.replace("sys.argv[1]", repr(clock))))
    assert got["spans"] == ["coded.panel", "coded.launch", "coded.call"] * 2
    if annotated:
        assert sorted(e[0] for e in got["events"]) == [
            "coded.call", "coded.launch", "coded.panel"]
        assert dict(got["events"])["coded.call"] == {"ordinal": "2"}
    else:
        assert got["events"] == []


def test_kernels_record_no_call_counter_or_eager_span():
    """An eager kernel call with the session on leaves no kernel.* span and
    no kernel.call counter; the kernels' time is read from their scope."""
    import jax.numpy as jnp

    from repro.kernels import ops

    try:
        sess = obs.enable(fresh=True)
        ops.matmul_t(jnp.ones((8, 8)), jnp.ones((8, 8)))
        assert sess.registry.total("kernel.call") == 0
        assert [s for s in sess.recorder.spans if s.name.startswith("kernel.")] == []
    finally:
        obs.disable()
