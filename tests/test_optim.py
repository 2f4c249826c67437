"""Optimizer, schedules, gradient compression, checkpointing."""

import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim.optimizer import (AdamWConfig, adamw_update,
                                   clip_by_global_norm, compress_int8,
                                   cosine_schedule, decompress_int8,
                                   init_adamw)
from repro.train.checkpoint import (available_steps, latest_step,
                                    restore_checkpoint, save_checkpoint)


class TestAdamW:
    def test_quadratic_converges(self):
        cfg = AdamWConfig(lr=0.1, warmup_steps=0, total_steps=200,
                          weight_decay=0.0, clip_norm=100.0)
        params = {"w": jnp.asarray([5.0, -3.0])}
        state = init_adamw(params)

        def loss(p):
            return jnp.sum(p["w"] ** 2)

        for _ in range(150):
            g = jax.grad(loss)(params)
            params, state, _ = adamw_update(cfg, params, g, state)
        assert float(loss(params)) < 1e-2

    def test_clip(self):
        g = {"a": jnp.asarray([3.0, 4.0])}
        clipped, norm = clip_by_global_norm(g, 1.0)
        assert float(norm) == pytest.approx(5.0)
        np.testing.assert_allclose(np.asarray(clipped["a"]),
                                   [0.6, 0.8], rtol=1e-5)

    def test_schedule_shape(self):
        cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100)
        s = [float(cosine_schedule(cfg, jnp.float32(t)))
             for t in (0, 5, 10, 55, 100)]
        assert s[0] == 0.0
        assert s[1] == pytest.approx(0.5)
        assert s[2] == pytest.approx(1.0)
        assert 0 < s[3] < 1.0
        assert s[4] == pytest.approx(0.0, abs=1e-6)

    def test_weight_decay_shrinks(self):
        cfg = AdamWConfig(lr=0.1, warmup_steps=0, weight_decay=0.5,
                          total_steps=10)
        params = {"w": jnp.asarray([10.0])}
        state = init_adamw(params)
        g = {"w": jnp.asarray([0.0])}
        p2, _, _ = adamw_update(cfg, params, g, state)
        assert float(p2["w"][0]) < 10.0


class TestCompression:
    def test_int8_roundtrip_error_bounded(self):
        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.normal(size=(1000,)).astype(np.float32))
        q, s = compress_int8(g)
        back = decompress_int8(q, s)
        err = np.abs(np.asarray(back - g)).max()
        assert err <= float(s) * 0.5 + 1e-6

    def test_compressed_psum_with_error_feedback(self):
        # single-device shard_map still exercises the psum path
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.optim.optimizer import compressed_psum
        mesh = make_mesh((1,), ("d",))
        g = {"w": jnp.asarray(np.random.default_rng(1)
                              .normal(size=(64,)).astype(np.float32))}
        r = {"w": jnp.zeros((64,), jnp.float32)}

        def f(g, r):
            return compressed_psum(g, "d", r)

        out, res = jax.shard_map(f, mesh=mesh, in_specs=(P(), P()),
                                 out_specs=(P(), P()),
                                 check_vma=False)(g, r)
        # sum over 1 device == dequantized value; error feedback bounded
        np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(g["w"]),
                                   atol=0.05)
        assert float(jnp.abs(res["w"]).max()) < 0.05

    def test_error_feedback_converges_over_steps(self):
        # repeated compression of a CONSTANT gradient: error feedback makes
        # the time-average exact
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.optim.optimizer import compressed_psum
        mesh = make_mesh((1,), ("d",))
        g = {"w": jnp.asarray([0.3, -0.7, 1.234, 0.001])}
        r = {"w": jnp.zeros((4,))}
        f = jax.shard_map(lambda g, r: compressed_psum(g, "d", r),
                          mesh=mesh, in_specs=(P(), P()),
                          out_specs=(P(), P()), check_vma=False)
        acc = np.zeros(4)
        for t in range(50):
            out, r = f(g, r)
            acc += np.asarray(out["w"])
        np.testing.assert_allclose(acc / 50, np.asarray(g["w"]), atol=2e-3)


class TestCheckpoint:
    def test_save_restore_roundtrip(self):
        tree = {"a": jnp.arange(10, dtype=jnp.float32),
                "b": {"c": jnp.ones((3, 4), jnp.bfloat16),
                      "d": jnp.zeros((), jnp.int32)},
                "lst": [jnp.asarray([1.0]), jnp.asarray([2.0])]}
        with tempfile.TemporaryDirectory() as tmp:
            save_checkpoint(tmp, 7, tree, extras={"note": "hi"})
            out, extras = restore_checkpoint(tmp, tree)
            assert extras["note"] == "hi"
            for a, b in zip(jax.tree_util.tree_leaves(tree),
                            jax.tree_util.tree_leaves(out)):
                assert np.asarray(a).dtype == np.asarray(b).dtype
                np.testing.assert_array_equal(
                    np.asarray(a, dtype=np.float64),
                    np.asarray(b, dtype=np.float64))

    def test_latest_pointer_and_retention(self):
        tree = {"x": jnp.ones((4,))}
        with tempfile.TemporaryDirectory() as tmp:
            for step in (1, 2, 3, 4, 5):
                save_checkpoint(tmp, step, tree, keep=3)
            assert latest_step(tmp) == 5
            assert available_steps(tmp) == [3, 4, 5]

    def test_corrupt_tmp_ignored(self):
        tree = {"x": jnp.ones((4,))}
        with tempfile.TemporaryDirectory() as tmp:
            save_checkpoint(tmp, 1, tree)
            # simulate a crashed mid-write checkpoint
            os.makedirs(os.path.join(tmp, "step_000000009.tmp"))
            assert latest_step(tmp) == 1
            out, _ = restore_checkpoint(tmp, tree)
            assert out is not None

    def test_structure_mismatch_raises(self):
        with tempfile.TemporaryDirectory() as tmp:
            save_checkpoint(tmp, 1, {"x": jnp.ones((4,))})
            with pytest.raises(AssertionError):
                restore_checkpoint(tmp, {"x": jnp.ones((4,)),
                                         "y": jnp.ones((2,))})
