"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs ref.py oracles."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.kernels.block_matmul import matmul_t_pallas


def _tol(dtype):
    return {"bfloat16": 2e-2, "float32": 2e-5, "float64": 1e-12}[np.dtype(dtype).name]


class TestEncodeKernel:
    @pytest.mark.parametrize("K,P,E", [(4, 4, 256), (10, 8, 2048),
                                       (16, 16, 4096), (3, 6, 1000),
                                       (1, 1, 128)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_sweep(self, rng, K, P, E, dtype):
        coeff = jnp.asarray(rng.normal(size=(K, P)), dtype)
        blocks = jnp.asarray(rng.normal(size=(P, E)), dtype)
        out = ops.encode(coeff, blocks)
        exp = ref.encode_ref(coeff, blocks)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(exp, np.float32),
            rtol=_tol(dtype), atol=_tol(dtype))

    def test_non_pow2_padding(self, rng):
        coeff = jnp.asarray(rng.normal(size=(5, 3)), jnp.float32)
        blocks = jnp.asarray(rng.normal(size=(3, 777)), jnp.float32)
        out = ops.encode(coeff, blocks)
        exp = ref.encode_ref(coeff, blocks)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=2e-5)

    def test_complex_falls_back_to_ref(self, rng):
        coeff = jnp.asarray(rng.normal(size=(4, 4)) + 1j * rng.normal(size=(4, 4)))
        blocks = jnp.asarray(rng.normal(size=(4, 64)), jnp.float32)
        out = ops.encode(coeff, blocks)
        exp = ref.encode_ref(coeff, blocks.astype(coeff.dtype))
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=1e-6)


class TestDecodeKernel:
    @pytest.mark.parametrize("mn,tau,E", [(4, 4, 512), (4, 9, 2048),
                                          (6, 11, 1024), (1, 1, 128)])
    def test_sweep(self, rng, mn, tau, E):
        W = jnp.asarray(rng.normal(size=(mn, tau)), jnp.float32)
        Y = jnp.asarray(rng.integers(-50, 50, size=(tau, E)), jnp.float32)
        for s in (64.0, 1024.0):
            out = ops.decode(W, Y, s)
            exp = ref.decode_ref(W, Y, s)
            np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))

    def test_extract_false_polycode_path(self, rng):
        W = jnp.asarray(rng.normal(size=(4, 9)), jnp.float32)
        Y = jnp.asarray(rng.integers(-50, 50, size=(9, 256)), jnp.float32)
        out = ops.decode(W, Y, 64.0, extract=False)
        exp = jnp.round(W @ Y)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))


class TestBlockMatmulKernel:
    @pytest.mark.parametrize("v,r,t", [(128, 128, 128), (512, 256, 384),
                                       (300, 200, 150), (64, 640, 64),
                                       (1024, 128, 128)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_sweep(self, rng, v, r, t, dtype):
        A = jnp.asarray(rng.normal(size=(v, r)), dtype)
        B = jnp.asarray(rng.normal(size=(v, t)), dtype)
        out = ops.matmul_t(A, B)
        exp = ref.matmul_t_ref(A, B)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(exp, np.float32),
            rtol=_tol(dtype) * v ** 0.5, atol=_tol(dtype) * v ** 0.5)

    @pytest.mark.parametrize("bm,bn,bk", [(128, 128, 128), (64, 128, 256)])
    def test_block_shapes(self, rng, bm, bn, bk):
        A = jnp.asarray(rng.normal(size=(512, 256)), jnp.float32)
        B = jnp.asarray(rng.normal(size=(512, 256)), jnp.float32)
        out = matmul_t_pallas(A, B, bm=bm, bn=bn, bk=bk, interpret=True)
        exp = ref.matmul_t_ref(A, B)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   rtol=1e-3, atol=1e-3)


class TestMambaScanKernel:
    @pytest.mark.parametrize("B,S,d,s,chunk,d_blk", [
        (2, 64, 32, 8, 16, 16), (1, 128, 16, 4, 32, 16),
        (3, 48, 24, 16, 16, 8)])
    def test_fwd_sweep(self, rng, B, S, d, s, chunk, d_blk):
        import jax
        import jax.numpy as jnp
        from repro.kernels.mamba_scan import mamba_scan_pallas
        dt = jnp.asarray(jax.nn.softplus(rng.normal(size=(B, S, d))), jnp.float32)
        x = jnp.asarray(rng.normal(size=(B, S, d)), jnp.float32)
        Bm = jnp.asarray(rng.normal(size=(B, S, s)), jnp.float32)
        Cm = jnp.asarray(rng.normal(size=(B, S, s)), jnp.float32)
        A_log = jnp.asarray(rng.uniform(0.1, 1.0, size=(d, s)), jnp.float32)
        D = jnp.asarray(rng.normal(size=(d,)), jnp.float32)
        y, hf, _ = mamba_scan_pallas(dt, x, Bm, Cm, A_log, D, chunk=chunk,
                                     d_blk=d_blk, interpret=True)
        y0, h0 = ref.mamba_scan_ref(dt, x, Bm, Cm, A_log, D)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y0),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(hf), np.asarray(h0),
                                   rtol=1e-4, atol=1e-4)

    def test_custom_vjp_matches_autodiff(self, rng):
        import jax
        import jax.numpy as jnp
        from repro.models.mamba import mamba_scan_fused
        B, S, d, s = 2, 32, 16, 4
        dt = jnp.asarray(jax.nn.softplus(rng.normal(size=(B, S, d))), jnp.float32)
        x = jnp.asarray(rng.normal(size=(B, S, d)), jnp.float32)
        Bm = jnp.asarray(rng.normal(size=(B, S, s)), jnp.float32)
        Cm = jnp.asarray(rng.normal(size=(B, S, s)), jnp.float32)
        A_log = jnp.asarray(rng.uniform(0.1, 1.0, size=(d, s)), jnp.float32)
        D = jnp.asarray(rng.normal(size=(d,)), jnp.float32)

        def loss_fused(*a):
            y, hf = mamba_scan_fused(*a)
            return jnp.sum(jnp.sin(y)) + 0.3 * jnp.sum(hf)

        def loss_ref(*a):
            y, hf = ref.mamba_scan_ref(*a)
            return jnp.sum(jnp.sin(y)) + 0.3 * jnp.sum(hf)

        g1 = jax.grad(loss_fused, argnums=tuple(range(6)))(dt, x, Bm, Cm, A_log, D)
        g0 = jax.grad(loss_ref, argnums=tuple(range(6)))(dt, x, Bm, Cm, A_log, D)
        for a, b in zip(g1, g0):
            sc = float(jnp.max(jnp.abs(b))) + 1e-9
            assert float(jnp.max(jnp.abs(a - b))) / sc < 1e-4


class TestWkvScanKernel:
    @pytest.mark.parametrize("B,S,H,dk,chunk", [(2, 64, 3, 8, 16),
                                                (1, 48, 2, 16, 8)])
    def test_fwd_sweep(self, rng, B, S, H, dk, chunk):
        import jax.numpy as jnp
        from repro.kernels.wkv_scan import wkv_scan_pallas
        from repro.models.rwkv6 import _wkv_chunked
        w = jnp.asarray(np.exp(-np.exp(rng.normal(size=(B, S, H, dk)))), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, S, H, dk)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, S, H, dk)), jnp.float32)
        r = jnp.asarray(rng.normal(size=(B, S, H, dk)), jnp.float32)
        u = jnp.asarray(rng.normal(size=(H, dk)), jnp.float32)
        y1, s1, _ = wkv_scan_pallas(w, k, v, r, u, chunk=chunk, interpret=True)
        S0 = jnp.zeros((B, H, dk, dk), jnp.float32)
        y0, s0 = _wkv_chunked(w, k, v, r, u, S0, 16)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y0),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s0),
                                   rtol=1e-4, atol=1e-4)

    def test_custom_vjp_matches_autodiff(self, rng):
        import jax
        import jax.numpy as jnp
        from repro.models.rwkv6 import _wkv_chunked, wkv_fused
        B, S, H, dk = 2, 32, 2, 8
        w = jnp.asarray(np.exp(-np.exp(rng.normal(size=(B, S, H, dk)))), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, S, H, dk)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, S, H, dk)), jnp.float32)
        r = jnp.asarray(rng.normal(size=(B, S, H, dk)), jnp.float32)
        u = jnp.asarray(rng.normal(size=(H, dk)), jnp.float32)
        S0 = jnp.zeros((B, H, dk, dk), jnp.float32)

        def lf(*a):
            y, sf = wkv_fused(*a)
            return jnp.sum(jnp.sin(y)) + 0.3 * jnp.sum(sf)

        def lr(*a):
            y, sf = _wkv_chunked(*a, S0, 16)
            return jnp.sum(jnp.sin(y)) + 0.3 * jnp.sum(sf)

        g1 = jax.grad(lf, argnums=tuple(range(5)))(w, k, v, r, u)
        g0 = jax.grad(lr, argnums=tuple(range(5)))(w, k, v, r, u)
        for a, b in zip(g1, g0):
            sc = float(jnp.max(jnp.abs(b))) + 1e-9
            assert float(jnp.max(jnp.abs(a - b))) / sc < 1e-4

    def test_rwkv_model_parity(self, rng):
        """Full rwkv6 smoke model: kernel path == chunked path."""
        import dataclasses

        import jax
        import jax.numpy as jnp
        from repro.configs import get_smoke_config
        from repro.models import init_params, train_loss
        cfg0 = get_smoke_config("rwkv6_3b")
        cfg1 = dataclasses.replace(cfg0, rwkv_kernel=True)
        key = jax.random.PRNGKey(0)
        params = init_params(cfg0, key)
        batch = {"tokens": jax.random.randint(key, (2, 32), 0, cfg0.vocab),
                 "labels": jax.random.randint(key, (2, 32), 0, cfg0.vocab)}
        l0 = jax.jit(lambda p: train_loss(p, cfg0, batch))(params)
        l1 = jax.jit(lambda p: train_loss(p, cfg1, batch))(params)
        assert abs(float(l0) - float(l1)) < 5e-3


class TestKernelPipelineEndToEnd:
    """encode -> worker matmul -> decode through the kernels == coded_matmul."""

    def test_full_pipeline(self, rng):
        import jax as _jax
        _jax.config.update("jax_enable_x64", True)
        from repro.core import make_plan, uncoded_matmul
        from repro.core.partition import block_decompose, block_recompose, unpad
        from repro.core.vandermonde import inverse_vandermonde

        v, r, t = 64, 48, 40
        A = jnp.asarray(rng.integers(-4, 5, size=(v, r)), jnp.float64)
        B = jnp.asarray(rng.integers(-4, 5, size=(v, t)), jnp.float64)
        L = v * 4 * 4 + 1
        plan = make_plan("bec", 2, 2, 2, K=6, L=L, points="chebyshev")
        g = plan.scheme.grid
        ab = block_decompose(A, g.p, g.m)
        bb = block_decompose(B, g.p, g.n)
        bv, br = ab.shape[2], ab.shape[3]
        bt = bb.shape[3]
        coeff_a = jnp.asarray(plan.coeff_a.reshape(plan.K, -1))
        coeff_b = jnp.asarray(plan.coeff_b.reshape(plan.K, -1))
        at = ops.encode(coeff_a, ab.reshape(g.p * g.m, -1)).reshape(plan.K, bv, br)
        btl = ops.encode(coeff_b, bb.reshape(g.p * g.n, -1)).reshape(plan.K, bv, bt)
        Y = jnp.stack([ops.matmul_t(at[k], btl[k]) for k in range(plan.tau)])
        Winv = inverse_vandermonde(plan.z_points[: plan.tau])
        useful = plan.scheme.useful_z_exp().reshape(-1)
        W = jnp.asarray(Winv[useful])
        C_blocks = ops.decode(W, Y.reshape(plan.tau, -1), plan.s)
        C = block_recompose(C_blocks.reshape(g.m, g.n, br, bt))
        C = unpad(C, (r, t))
        np.testing.assert_array_equal(np.asarray(C),
                                      np.asarray(uncoded_matmul(A, B)))


class TestKernelDtypeRules:
    """Complex operands run the oracle, counted; float64 never compiles
    for the chip (Pallas TPU has no f64) and the error names the way out."""

    def test_complex_fallback_is_counted(self, rng):
        from repro import obs

        c = jnp.asarray(rng.normal(size=(4, 4)) + 1j * rng.normal(size=(4, 4)))
        blocks = jnp.asarray(rng.normal(size=(4, 64)), jnp.float32)
        obs.enable(fresh=True)
        try:
            ops.encode(c, blocks)
            ops.matmul_t(c, c)
            reg = obs.session().registry
            assert reg.value("kernel.fallback", op="encode") == 1
            assert reg.value("kernel.fallback", op="matmul_t") == 1
            assert reg.total("kernel.fallback") == 2
            ops.encode(c.real.astype(jnp.float32), blocks)  # a kernel call
            assert reg.total("kernel.fallback") == 2
        finally:
            obs.disable()

    @pytest.mark.parametrize("op", ["encode", "decode", "decode_partial",
                                    "fused_worker", "matmul_t"])
    def test_float64_kernel_for_the_chip_raises(self, monkeypatch, op):
        from repro.core.numerics import enable_x64

        monkeypatch.setattr(ops, "_interpret", lambda: False)
        with enable_x64():
            x = jnp.ones((4, 8), jnp.float64)
            args = {"encode": (x, x.T),
                    "decode": (x, x.T, 64.0),
                    "decode_partial": (x[None], x.T[None], 64.0),
                    "fused_worker": (x, x, x.reshape(8, 2, 2),
                                     x.reshape(8, 2, 2)),
                    "matmul_t": (x, x)}[op]
            with pytest.raises(TypeError, match="backend='reference'"):
                getattr(ops, op)(*args)


class TestFloat64ProductOnTheChip:
    """The float64 worker product a TPU takes (int8 slices, exact int32
    sums), run here on the CPU against NumPy and the plain einsum path."""

    @pytest.mark.parametrize("data", ["int20", "normal", "wide-scales"])
    def test_sliced_matches_numpy(self, rng, data):
        from repro.core.numerics import enable_x64, sliced_matmul_t

        n = 4000
        if data == "int20":
            a = rng.integers(0, 2 ** 20, (n, 24)).astype(np.float64)
            b = rng.integers(-2 ** 20, 2 ** 20, (n, 16)).astype(np.float64)
        else:
            a = rng.standard_normal((n, 24))
            b = rng.standard_normal((n, 16))
            if data == "wide-scales":
                a *= np.exp2(rng.integers(-60, 60, (1, 24)))
                a[:, 0] = 0.0
        exp = a.T @ b
        with enable_x64():
            got = np.asarray(jax.jit(sliced_matmul_t)(jnp.asarray(a),
                                                      jnp.asarray(b)))
        if data == "int20":                  # every partial sum is exact
            np.testing.assert_array_equal(got, exp)
        else:                                # as close as an f64 dot gets
            err = np.abs(got - exp) / np.max(np.abs(exp), axis=1,
                                              keepdims=True).clip(1e-300)
            assert np.max(err) < 1e-14, np.max(err)

    @pytest.mark.parametrize("kind,erased", [("bec", [1, 2, 4, 5, 7, 8]),
                                             ("polycode", [4])])
    def test_reference_executor_exact_on_the_chip_path(self, monkeypatch,
                                                       kind, erased):
        from repro.configs.paper_matmul import SMOKE as g
        from repro.core import make_plan, numerics
        from repro.runtime import CodedMatmul

        rng = np.random.default_rng(1)
        A = rng.integers(0, g.entry_max + 1, (g.v, g.r)).astype(np.float64)
        B = rng.integers(0, g.entry_max + 1, (g.v, g.t)).astype(np.float64)
        plan = make_plan(kind, g.p, g.m, g.n, K=g.K, L=g.L, points=g.points)
        monkeypatch.setattr(numerics, "_emulated_f64", lambda: True)
        with numerics.enable_x64():
            cm = CodedMatmul(plan, "reference", dtype=jnp.float64)
            C = np.asarray(cm(jnp.asarray(A), jnp.asarray(B), erased=erased))
        np.testing.assert_array_equal(C, A.T @ B)
