"""Multi-device tests (8 fake CPU devices via subprocess - the main test
process must keep seeing ONE device, so anything needing a mesh runs in a
child interpreter with XLA_FLAGS set before jax imports)."""
import os
import subprocess
import sys
from pathlib import Path

import pytest


SRC = str(Path(__file__).resolve().parents[1] / "src")


def run_child(code: str, devices: int = 8, timeout: int = 900):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise AssertionError(f"child failed:\n{proc.stdout}\n{proc.stderr}")
    return proc.stdout


class TestCodedMesh:
    def test_erasure_tolerant_exact(self):
        out = run_child("""
import jax; jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp, numpy as np
from repro.core import make_plan, uncoded_matmul
from repro.distributed.coded import coded_matmul_mesh
mesh = jax.make_mesh((2, 4), ("data", "model"))
rng = np.random.default_rng(0)
A = jnp.asarray(rng.integers(-4, 5, size=(64, 48)), jnp.float64)
B = jnp.asarray(rng.integers(-4, 5, size=(64, 40)), jnp.float64)
plan = make_plan("bec", 2, 2, 1, K=4, L=64*4*4+1, points="chebyshev")
C0 = uncoded_matmul(A, B)
for erased in ([], [1], [0, 3]):
    mask = np.ones(4); mask[erased] = 0
    C = coded_matmul_mesh(A, B, plan, mesh, jnp.asarray(mask), dtype=jnp.float64)
    assert float(jnp.max(jnp.abs(C - C0))) == 0.0, erased
print("OK")
""")
        assert "OK" in out

    def test_coded_linear_quantized_grid_exact(self):
        out = run_child("""
import jax; jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp, numpy as np
from repro.core import make_plan
from repro.distributed.coded import CodedLinearPlan
mesh = jax.make_mesh((2, 4), ("data", "model"))
rng = np.random.default_rng(1)
x = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
W = jnp.asarray(rng.normal(size=(32, 24)), jnp.float32)
plan = make_plan("bec", 2, 2, 1, K=4, L=32*7*7+1, points="chebyshev")
lin = CodedLinearPlan(plan, mesh, quant_bits=4, dtype=jnp.float64)
y = lin(x, W, mask=jnp.asarray([1., 0., 1., 1.]))
# compare against the QUANTIZED reference: the coded path itself is exact
qmax = 7
sx = float(jnp.max(jnp.abs(x))) / qmax + 1e-9
sw = float(jnp.max(jnp.abs(W))) / qmax + 1e-9
y_ref = (jnp.round(x / sx) @ jnp.round(W / sw)) * (sx * sw)
assert float(jnp.max(jnp.abs(y - y_ref))) < 1e-6
print("OK")
""")
        assert "OK" in out


class TestMeshPartial:
    """Partial-straggler sub-tasking on the mesh backend.

    Parity bar: ("partial", Q) output bit-identical to the reference
    executor for the same progress vector across all three scheme
    families, Q = 1 bit-identical to the legacy mesh erasure path, zero
    recompiles across progress changes, non-spanning vectors raise."""

    def test_partial_parity_all_schemes(self):
        out = run_child("""
import jax; jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp, numpy as np
from repro.core import make_plan, make_scheme, uncoded_matmul
from repro.runtime import CodedMatmul, MeshExecutor

def spanning(K, Q):
    prog = np.ones(K)
    if Q == 1:
        prog[0] = 0.0
    else:
        prog[0] = prog[1] = (Q - 1) / Q
    return prog

rng = np.random.default_rng(0)
for kind, p, m, n, pp in [("bec", 2, 2, 2, 1), ("tradeoff", 4, 2, 1, 2),
                          ("polycode", 2, 2, 1, 1)]:
    tau = make_scheme(kind, p, m, n, p_prime=pp).tau
    v = 8 * p
    plan = make_plan(kind, p, m, n, K=tau + 2, L=v * 3 * 3 + 1, p_prime=pp)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:plan.K]), ("model",))
    cm_mesh = CodedMatmul(plan, MeshExecutor(mesh, use_kernels=False),
                          dtype=jnp.float64)
    cm_ref = CodedMatmul(plan, "reference", dtype=jnp.float64)
    A = jnp.asarray(rng.integers(-3, 4, size=(v, 12)), jnp.float64)
    B = jnp.asarray(rng.integers(-3, 4, size=(v, 10)), jnp.float64)
    C0 = np.asarray(uncoded_matmul(A, B))
    for Q in (1, 2, 4):
        prog = spanning(plan.K, Q)
        Cm = np.asarray(cm_mesh(A, B, progress=prog, sub_tasks=Q))
        Cr = np.asarray(cm_ref(A, B, progress=prog, sub_tasks=Q))
        assert np.array_equal(Cm, Cr), (kind, Q)
        assert np.array_equal(Cm, C0), (kind, Q)
    # Q = 1 partial must be bit-identical to the legacy binary mesh path
    Cb = np.asarray(cm_mesh(A, B, erased=[0]))
    Cq1 = np.asarray(cm_mesh(A, B, progress=spanning(plan.K, 1), sub_tasks=1))
    assert np.array_equal(Cb, Cq1), kind
print("OK")
""")
        assert "OK" in out

    def test_partial_traced_zero_recompiles_and_raise_parity(self):
        out = run_child("""
import jax; jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp, numpy as np
from repro.core import make_plan, make_scheme, uncoded_matmul
from repro.runtime import CodedMatmul, MeshExecutor

tau = make_scheme("bec", 2, 2, 2, p_prime=1).tau
v = 16
plan = make_plan("bec", 2, 2, 2, K=tau + 2, L=v * 3 * 3 + 1, p_prime=1)
mesh = jax.sharding.Mesh(np.array(jax.devices()[:plan.K]), ("model",))
cm = CodedMatmul(plan, MeshExecutor(mesh, use_kernels=False),
                 dtype=jnp.float64)
rng = np.random.default_rng(1)
A = jnp.asarray(rng.integers(-3, 4, size=(v, 12)), jnp.float64)
B = jnp.asarray(rng.integers(-3, 4, size=(v, 10)), jnp.float64)
C0 = np.asarray(uncoded_matmul(A, B))
for Q in (2, 4):
    f = jax.jit(lambda a, b, w: cm(a, b, progress=w, sub_tasks=Q))
    prog = np.ones(plan.K); prog[0] = prog[1] = (Q - 1) / Q
    assert np.array_equal(np.asarray(f(A, B, jnp.asarray(prog))), C0), Q
    prog2 = np.ones(plan.K); prog2[2] = (Q - 1) / Q
    assert np.array_equal(np.asarray(f(A, B, jnp.asarray(prog2))), C0), Q
# one executable per Q; progress changes hit the memo, never rebuild
info = cm.cache_info()
assert info["builds"] == 2, info
# concrete progress changes reuse the traced-free ("partial", Q) pipeline
for trial in range(4):
    prog = np.ones(plan.K)
    prog[trial % plan.K] = 0.5
    assert np.array_equal(np.asarray(cm(A, B, progress=prog, sub_tasks=2)),
                          C0), trial
assert cm.cache_info()["builds"] == 3, cm.cache_info()
# non-spanning raise parity with the reference executor
bad = np.zeros(plan.K); bad[:plan.tau - 1] = 1.0
for backend in (cm, CodedMatmul(plan, "reference", dtype=jnp.float64)):
    try:
        backend(A, B, progress=bad, sub_tasks=2)
        raise SystemExit("non-spanning progress did not raise")
    except ValueError as e:
        assert "span" in str(e), e
print("OK")
""")
        assert "OK" in out

    def test_partial_parity_with_kernels(self):
        out = run_child("""
import jax; jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp, numpy as np
from repro.core import make_plan, make_scheme, uncoded_matmul
from repro.runtime import CodedMatmul, MeshExecutor

tau = make_scheme("bec", 2, 2, 1, p_prime=1).tau
v = 8
plan = make_plan("bec", 2, 2, 1, K=tau + 2, L=v * 3 * 3 + 1, p_prime=1)
mesh = jax.sharding.Mesh(np.array(jax.devices()[:plan.K]), ("model",))
cm = CodedMatmul(plan, MeshExecutor(mesh), dtype=jnp.float64)
rng = np.random.default_rng(2)
A = jnp.asarray(rng.integers(-3, 4, size=(v, 6)), jnp.float64)
B = jnp.asarray(rng.integers(-3, 4, size=(v, 6)), jnp.float64)
C0 = np.asarray(uncoded_matmul(A, B))
prog = np.ones(plan.K); prog[0] = prog[1] = 0.5
C = np.asarray(cm(A, B, progress=prog, sub_tasks=2))
assert np.array_equal(C, C0)
print("OK")
""")
        assert "OK" in out


class TestMeshOperandLayout:
    """A and B enter the mesh program as row shards of v over ``model`` and
    are all-gathered inside it; where K does not split v, whole.  The
    results stay bit-identical to the reference backend."""

    SCHEMES = {
        # scheme: (p, m, n, K, v); v splits over the K workers
        "bec": (2, 2, 1, 4, 24),
        "polycode": (2, 2, 1, 6, 24),
    }

    @pytest.mark.parametrize("scheme", sorted(SCHEMES))
    def test_sharded_bit_identical_to_reference(self, scheme):
        p, m, n, K, v = self.SCHEMES[scheme]
        out = run_child(f"""
import itertools
import jax; jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, Mesh
from repro.core import make_plan
from repro.runtime import CodedMatmul, MeshExecutor

K, v = {K}, {v}
plan = make_plan("{scheme}", {p}, {m}, {n}, K=K, L=v * 4 * 4 + 1,
                 points="chebyshev")
mesh = Mesh(np.array(jax.devices()[:K]).reshape(1, K), ("data", "model"),
            axis_types=(AxisType.Auto,) * 2)
ex = MeshExecutor(mesh, use_kernels=False)
assert ex.operand_layout(v) == "sharded"
cm = CodedMatmul(plan, ex, dtype=jnp.float64)
ref = CodedMatmul(plan, "reference", dtype=jnp.float64)
rng = np.random.default_rng(3)
A = jnp.asarray(rng.integers(-4, 5, size=(v, 12)), jnp.float64)
B = jnp.asarray(rng.integers(-4, 5, size=(v, 10)), jnp.float64)
C0 = np.asarray(A).T @ np.asarray(B)
traced = jax.jit(lambda a, b, mk: cm(a, b, mask=mk))
patterns = [e for k in range(K - plan.tau + 1)
            for e in itertools.combinations(range(K), k)]
for erased in patterns:
    mask = np.ones(K); mask[list(erased)] = 0.0
    Cr = np.asarray(ref(A, B, erased=list(erased)))
    assert np.array_equal(Cr, C0), erased
    Cc = cm(A, B, erased=list(erased))
    assert Cc.sharding.is_fully_replicated, Cc.sharding
    assert np.array_equal(np.asarray(Cc), Cr), ("concrete", erased)
    Ct = np.asarray(traced(A, B, jnp.asarray(mask)))
    assert np.array_equal(Ct, Cr), ("traced", erased)
for Q in (1, 2):
    prog = np.ones(K); prog[0] = (Q - 1) / Q
    Cp = np.asarray(cm(A, B, progress=prog, sub_tasks=Q))
    assert np.array_equal(Cp, np.asarray(ref(A, B, progress=prog, sub_tasks=Q))), Q
    assert np.array_equal(Cp, C0), Q
print("OK", len(patterns))
""")
        assert "OK" in out

    def test_single_device_operands_enter_row_sharded(self):
        out = run_child("""
import jax; jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
from repro.core import make_plan
from repro.runtime import CodedMatmul, MeshExecutor

mesh = Mesh(np.array(jax.devices()[:4]).reshape(1, 4), ("data", "model"),
            axis_types=(AxisType.Auto,) * 2)
plan = make_plan("bec", 2, 2, 1, K=4, L=32 * 16 + 1, points="chebyshev")
ex = MeshExecutor(mesh, use_kernels=False)
cm = CodedMatmul(plan, ex, dtype=jnp.float64)
rng = np.random.default_rng(4)
A = jax.device_put(jnp.asarray(rng.integers(-4, 5, size=(32, 12)), jnp.float64),
                   jax.devices()[0])
B = jnp.asarray(rng.integers(-4, 5, size=(32, 10)), jnp.float64)  # uncommitted
C = cm(A, B, erased=[1, 2])
assert np.array_equal(np.asarray(C), np.asarray(A).T @ np.asarray(B))
pa, pb = ex.place_operands(A, B)
for X in (pa, pb):
    assert X.sharding.spec == P("model", None), X.sharding
    assert {s.data.shape[0] for s in X.addressable_shards} == {8}
mask = jnp.asarray([1.0, 0.0, 0.0, 1.0])
W = jnp.asarray(cm.panel_cache.get(np.asarray(mask)).W)
(fn,) = cm._executables.values()
# the layout the program itself asks for, with no sharding given
shapes = [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (A, B, mask, W)]
compiled = fn.lower(*shapes).compile()
rows = NamedSharding(mesh, P("model", None))
for s in compiled.input_shardings[0][:2]:
    assert s.is_equivalent_to(rows, 2), s
# operands already on the mesh's devices keep their layout
on_mesh = jax.device_put(A, NamedSharding(mesh, P()))
kept_a, kept_b = ex.place_operands(on_mesh, pb)
assert kept_a is on_mesh and kept_b is pb
assert np.array_equal(np.asarray(cm(on_mesh, B, erased=[1, 2])), np.asarray(C))
print("OK")
""")
        assert "OK" in out

    def test_uneven_and_batched_operands_exact_and_counted(self):
        out = run_child("""
import jax; jax.config.update("jax_enable_x64", True)
import jax.numpy as jnp, numpy as np
from jax.sharding import AxisType, Mesh, PartitionSpec as P
from repro import obs
from repro.core import make_plan
from repro.runtime import CodedMatmul, MeshExecutor

mesh = Mesh(np.array(jax.devices()[:4]).reshape(1, 4), ("data", "model"),
            axis_types=(AxisType.Auto,) * 2)
plan = make_plan("bec", 2, 2, 1, K=4, L=34 * 16 + 1, points="chebyshev")
ex = MeshExecutor(mesh, use_kernels=False)
cm = CodedMatmul(plan, ex, dtype=jnp.float64)
ref = CodedMatmul(plan, "reference", dtype=jnp.float64)
rng = np.random.default_rng(5)
reg = obs.enable(fresh=True).registry

def ints(*shape):
    return jnp.asarray(rng.integers(-4, 5, size=shape), jnp.float64)

# v = 34: the workers do not split it, so A and B enter whole
A, B = ints(34, 12), ints(34, 10)
assert ex.operand_layout(34) == "replicated"
assert ex.operand_sharding(A.shape).spec == P()
C0 = np.asarray(A).T @ np.asarray(B)
for erased in ([], [0, 3], [1, 2]):
    C = np.asarray(cm(A, B, erased=erased))
    assert np.array_equal(C, C0) and np.array_equal(
        C, np.asarray(ref(A, B, erased=erased))), erased
assert np.array_equal(np.asarray(cm(A, B, progress=[1, 1, 0.5, 0.5],
                                    sub_tasks=2)), C0)
assert reg.value("mesh.operands", layout="replicated") == 4
assert reg.value("mesh.operands", layout="sharded") is None

# batched A (2, 32, r): v at position -2 is sharded, the batch is not
Ab, B2 = ints(2, 32, 12), ints(32, 10)
assert ex.operand_sharding(Ab.shape).spec == P(None, "model", None)
Cb = cm(Ab, B2, erased=[2])
for i in range(2):
    assert np.array_equal(np.asarray(Cb[i]), np.asarray(Ab[i]).T @ np.asarray(B2)), i
assert np.array_equal(np.asarray(Cb), np.asarray(ref(Ab, B2, erased=[2])))
cm(Ab[0], B2, erased=[0, 1])
assert reg.value("mesh.operands", layout="sharded") == 2
assert reg.value("mesh.operands", layout="replicated") == 4
print("OK")
""")
        assert "OK" in out


class TestMoEParallel:
    def test_ep_matches_dense(self):
        """EP (all_to_all shard_map) == dense oracle at high capacity."""
        out = run_child("""
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.sharding import axis_rules, default_rules
from repro.launch.mesh import make_debug_mesh
from repro.models.moe import MoEConfig, init_moe, apply_moe, _moe_dense
mesh = make_debug_mesh(2, 4)
rules = default_rules(mesh)
cfg = MoEConfig(n_experts=8, top_k=2, d_expert_ff=32, n_shared=1,
                capacity_factor=64.0)  # no drops
key = jax.random.PRNGKey(0)
params = init_moe(key, 16, cfg, ep_size=4, dtype=jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 16), jnp.float32)
y_dense, aux_d = _moe_dense(params, x, cfg)
with axis_rules(rules):
    y_ep, aux_e = jax.jit(lambda p, x: apply_moe(p, x, cfg))(params, x)
err = float(jnp.max(jnp.abs(y_dense - y_ep)))
rel = err / (float(jnp.max(jnp.abs(y_dense))) + 1e-9)
assert rel < 2e-2, (err, rel)
print("OK", rel)
""")
        assert "OK" in out

    def test_ep_capacity_drops_tokens(self):
        out = run_child("""
import jax, jax.numpy as jnp
from repro.distributed.sharding import axis_rules, default_rules
from repro.launch.mesh import make_debug_mesh
from repro.models.moe import MoEConfig, init_moe, apply_moe
mesh = make_debug_mesh(2, 4)
rules = default_rules(mesh)
cfg = MoEConfig(n_experts=8, top_k=2, d_expert_ff=32, capacity_factor=0.1)
params = init_moe(jax.random.PRNGKey(0), 16, cfg, ep_size=4, dtype=jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 16), jnp.float32)
with axis_rules(rules):
    y, aux = jax.jit(lambda p, x: apply_moe(p, x, cfg))(params, x)
assert bool(jnp.all(jnp.isfinite(y)))
print("OK")
""")
        assert "OK" in out


class TestShardedTraining:
    def test_mesh_train_step_matches_single_device(self):
        """One train step on a 2x4 mesh == single device (same math)."""
        out = run_child("""
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro.configs import get_smoke_config
from repro.distributed.sharding import axis_rules, default_rules
from repro.launch.steps import make_train_step
from repro.models import init_params
from repro.optim import OptConfig, adamw_init
cfg = dataclasses.replace(get_smoke_config("qwen3_0_6b"), tp_pad=4,
                          dtype="float32")
key = jax.random.PRNGKey(0)
params = init_params(cfg, key)
opt = adamw_init(params)
batch = {"tokens": jax.random.randint(key, (8, 64), 0, cfg.vocab),
         "labels": jax.random.randint(key, (8, 64), 0, cfg.vocab)}
ocfg = OptConfig()
# single device
p1, o1, m1 = jax.jit(make_train_step(cfg, ocfg, None))(params, opt, batch)
# mesh
from repro.launch.mesh import make_debug_mesh
mesh = make_debug_mesh(2, 4)
rules = default_rules(mesh)
p2, o2, m2 = jax.jit(make_train_step(cfg, ocfg, rules))(params, opt, batch)
l1, l2 = float(m1["loss"]), float(m2["loss"])
assert abs(l1 - l2) / abs(l1) < 1e-4, (l1, l2)
f32 = jnp.float32
d = jax.tree.map(
    lambda a, b: float(jnp.max(jnp.abs(a.astype(f32) - b.astype(f32)))),
    p1, p2)
mx = max(jax.tree.leaves(d))
assert mx < 1e-2, mx
print("OK", l1, l2, mx)
""", timeout=1200)
        assert "OK" in out
