"""The serving launcher's contract: exact answers, loud failures.

``repro.launch.coded_serve`` checks every request against the plain
``A^T B`` and raises on a mismatch; mode combinations it cannot serve are
argument errors, never a silent switch to another backend; and its
persistent compilation cache lives at one fixed place.
"""
from __future__ import annotations

import argparse
from pathlib import Path

import numpy as np
import pytest

import jax

from repro.launch import coded_serve, compile_cache

SMALL = ["--backend", "reference", "--requests", "2", "--size", "32"]


@pytest.fixture
def no_cache(monkeypatch):
    """Keep the persistent cache off in the test process."""
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")


def test_static_serve_returns_checked_results(no_cache):
    lat, outputs = coded_serve.main(SMALL)
    assert len(lat) == len(outputs) == 2
    assert all(C.shape == (16, 16) for C in outputs)


def test_failed_check_raises(no_cache, monkeypatch):
    monkeypatch.setattr(coded_serve, "_oracle",
                        lambda A, B: np.full((16, 16), np.nan))
    with pytest.raises(RuntimeError, match="request 0: coded C != A"):
        coded_serve.main(SMALL)


@pytest.mark.parametrize("argv", [
    ["--backend", "mesh", "--adaptive", "--elastic"],
    ["--backend", "mesh", "--serve-tier"],
], ids=["elastic-on-mesh", "serve-tier-on-mesh"])
def test_unservable_modes_are_argument_errors(no_cache, argv):
    with pytest.raises(SystemExit) as exc:
        coded_serve.main(argv)
    assert exc.value.code == 2


@pytest.mark.parametrize("tpu", [True, False], ids=["tpu", "off-tpu"])
def test_mesh_serves_float64_on_xla_where_pallas_lacks_it(monkeypatch, tpu):
    from repro.kernels import ops

    monkeypatch.setattr(ops, "on_tpu", lambda: tpu)
    backend = coded_serve._backend(argparse.Namespace(backend="mesh"), K=1)
    assert backend.use_kernels is not tpu
    assert coded_serve._describe(backend) == ("mesh[xla]" if tpu
                                              else "mesh[kernels]")


class TestCompileCache:
    @pytest.fixture(autouse=True)
    def restore_config(self):
        was = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", was)

    def test_environment_wins(self, monkeypatch):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/cache/from/env")
        was = jax.config.jax_compilation_cache_dir
        assert compile_cache.enable_compile_cache() == "/cache/from/env"
        assert jax.config.jax_compilation_cache_dir == was

    def test_default_is_a_fixed_ignored_path_in_the_checkout(self,
                                                              monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = compile_cache.enable_compile_cache()
        assert path == jax.config.jax_compilation_cache_dir
        root = Path(path).parent
        assert (root / "pyproject.toml").is_file()
        ignored = (root / ".gitignore").read_text().splitlines()
        assert Path(path).name + "/" in ignored
        assert compile_cache.enable_compile_cache() == path
