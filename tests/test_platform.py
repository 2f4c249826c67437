"""Backend-derived execution: Pallas interpret mode, the compile cache, and
the chip check's refusal to run without a TPU."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build_engine
from repro.graph import erdos_renyi
from repro.kernels import resolve_interpret
from repro.kernels.ema import ops as ema_ops
from repro.launch import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def tpu_backend(monkeypatch):
    """Make the code under test see a TPU default backend."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


class TestInterpretResolution:
    def test_cpu_backend_interprets(self):
        assert jax.default_backend() == "cpu"
        assert resolve_interpret() is True
        assert resolve_interpret(None) is True
        # CPU callers that ask explicitly keep what they ask for
        assert resolve_interpret(True) is True
        assert resolve_interpret(False) is False

    def test_tpu_backend_compiles(self, tpu_backend):
        assert resolve_interpret() is False
        assert resolve_interpret(False) is False
        # the compiled dtype table applies: the TPU vector unit has no f64
        assert ema_ops.pallas_dtype_pair(np.float64) is None
        assert ema_ops.pallas_dtype_pair(jnp.bfloat16) is not None

    def test_tpu_backend_refuses_explicit_interpret(self, tpu_backend):
        with pytest.raises(ValueError, match="interpret=True on a TPU"):
            resolve_interpret(True)

    def test_engine_resolves_through_backend(self, tpu_backend):
        g = erdos_renyi(40, 4.0, seed=0)
        assert build_engine(g, "u3", "pgbsc").interpret is False
        with pytest.raises(ValueError, match="interpret=True on a TPU"):
            build_engine(g, "u3", "pgbsc", interpret=True)

    def test_engine_interprets_on_cpu(self):
        g = erdos_renyi(40, 4.0, seed=0)
        assert build_engine(g, "u3", "pgbsc").interpret is True


@pytest.mark.parametrize("engine,kw", [
    ("pgbsc", {}),
    ("pgbsc", {"spmm_method": "pallas_bsr", "reorder": "rcm"}),
    ("pgbsc", {"fuse_spmm_ema": True}),
    ("fascia", {}),
], ids=["segment", "bsr_rcm", "fused", "fascia"])
def test_graph_operands_are_program_arguments(engine, kw):
    """The dispatch program takes the graph's device arrays as arguments.
    Closed over, they would be embedded in it as constants: at RMAT scale
    20 that is hundreds of MB of program to compile, and too large for the
    compilation cache."""
    g = erdos_renyi(300, 8.0, seed=0)
    e = build_engine(g, "u5", engine, **kw)
    e._ensure()
    closed = jax.make_jaxpr(e._seeded())(
        e._operands(), jnp.int32(0), jnp.arange(2, dtype=jnp.int32))
    largest = max((np.size(c) for c in closed.consts), default=0)
    assert largest < g.n, largest


class TestCompileCache:
    @pytest.fixture(autouse=True)
    def _restore_config(self):
        was = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", was)

    def test_env_dir_left_alone(self, monkeypatch, tmp_path):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        # JAX reads the variable itself; the helper sets nothing
        assert jax.config.jax_compilation_cache_dir is None

    def test_default_dir_fixed_in_checkout(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = compile_cache.CHECKOUT_CACHE_DIR
        assert os.path.dirname(want) == REPO
        paths = []
        for _ in range(2):
            jax.config.update("jax_compilation_cache_dir", None)
            paths.append(compile_cache.enable_compile_cache())
            assert jax.config.jax_compilation_cache_dir == want
        assert paths == [want, want]


def test_chip_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                          cwd=REPO, env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"ok"' not in proc.stdout
