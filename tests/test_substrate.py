"""Substrate tests: optimizer, checkpoint (atomic/async/elastic), data
pipeline determinism, gradient compression, sharding rules."""
import os
import tempfile

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.optim import adamw


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------
class TestAdamW:
    def test_quadratic_convergence(self):
        cfg = adamw.AdamWConfig(learning_rate=0.1, warmup_steps=1,
                                total_steps=300, weight_decay=0.0,
                                schedule="const", grad_clip=100.0)
        params = {"w": jnp.asarray([5.0, -3.0])}
        state = adamw.init(params)

        @jax.jit
        def step(params, state):
            loss, g = jax.value_and_grad(
                lambda p: jnp.sum(p["w"] ** 2))(params)
            p, s, m = adamw.update(cfg, params, g, state)
            return p, s, loss

        for _ in range(300):
            params, state, loss = step(params, state)
        assert float(loss) < 1e-3

    def test_grad_clip(self):
        g = {"w": jnp.full((4,), 100.0)}
        clipped, gn = adamw.clip_by_global_norm(g, 1.0)
        assert abs(float(adamw.global_norm(clipped)) - 1.0) < 1e-5
        assert float(gn) == pytest.approx(200.0)

    def test_schedule_warmup_and_decay(self):
        cfg = adamw.AdamWConfig(learning_rate=1.0, warmup_steps=10,
                                total_steps=100, schedule="cosine",
                                min_lr_frac=0.1)
        lrs = [float(adamw.lr_at(cfg, jnp.asarray(s)))
               for s in [0, 4, 9, 50, 99]]
        assert lrs[0] < lrs[1] < lrs[2]               # warming up
        assert lrs[2] == pytest.approx(1.0, rel=1e-3)
        assert lrs[3] > lrs[4]                        # decaying
        assert lrs[4] >= 0.1 * 0.99                   # floor

    def test_weight_decay_decoupled(self):
        cfg = adamw.AdamWConfig(learning_rate=0.1, weight_decay=0.5,
                                warmup_steps=1, schedule="const",
                                grad_clip=1e9)
        params = {"w": jnp.asarray([1.0])}
        state = adamw.init(params)
        zero_g = {"w": jnp.asarray([0.0])}
        p2, _, _ = adamw.update(cfg, params, zero_g, state)
        # pure decay step: w -> w * (1 - lr*wd)
        assert float(p2["w"][0]) == pytest.approx(1.0 - 0.1 * 0.5, rel=1e-4)


# ---------------------------------------------------------------------------
# checkpoint manager
# ---------------------------------------------------------------------------
class TestCheckpoint:
    def _tree(self):
        return {"params": {"a": jnp.arange(12.0).reshape(3, 4),
                           "nested": {"b": jnp.ones((5,), jnp.bfloat16)}},
                "opt": (jnp.zeros(3), jnp.ones(2))}

    def test_roundtrip_sync(self):
        from repro.checkpoint.manager import CheckpointManager
        with tempfile.TemporaryDirectory() as d:
            cm = CheckpointManager(d)
            cm.save(3, self._tree(), {"loss": 1.5})
            tree, meta = cm.restore()
            assert meta["step"] == 3 and meta["loss"] == 1.5
            np.testing.assert_array_equal(
                tree["params"]["a"], np.arange(12.0).reshape(3, 4))
            assert isinstance(tree["opt"], tuple)

    def test_async_and_retention(self):
        from repro.checkpoint.manager import CheckpointManager
        with tempfile.TemporaryDirectory() as d:
            cm = CheckpointManager(d, keep_last=2)
            for s in (1, 2, 3, 4):
                cm.save(s, self._tree(), asynchronous=True)
                cm.wait()
            assert cm.all_steps() == [3, 4]

    def test_atomicity_no_partial_dirs(self):
        from repro.checkpoint.manager import CheckpointManager
        with tempfile.TemporaryDirectory() as d:
            cm = CheckpointManager(d)
            cm.save(1, self._tree())
            # a stale tmp dir must never be listed as a checkpoint
            os.makedirs(os.path.join(d, "step_9.tmp"))
            assert cm.all_steps() == [1]
            assert cm.latest_step() == 1

    def test_elastic_restore_reshard(self):
        """Saved unsharded -> restored with explicit shardings (new mesh)."""
        from repro.checkpoint.manager import CheckpointManager
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((1,), ("model",))
        with tempfile.TemporaryDirectory() as d:
            cm = CheckpointManager(d)
            cm.save(1, self._tree())
            sh = NamedSharding(mesh, P())
            shardings = jax.tree.map(lambda _: sh, self._tree())
            tree, _ = cm.restore(shardings=shardings)
            leaf = tree["params"]["a"]
            assert isinstance(leaf, jax.Array)
            assert leaf.sharding == sh

    def test_restore_empty_dir(self):
        from repro.checkpoint.manager import CheckpointManager
        with tempfile.TemporaryDirectory() as d:
            cm = CheckpointManager(d)
            tree, meta = cm.restore()
            assert tree is None and meta is None


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------
class TestData:
    def test_determinism_and_step_dependence(self):
        from repro.data.pipeline import TokenPipeline
        tp = TokenPipeline(vocab=100, seq_len=32, global_batch=4, seed=1)
        b0a, b0b, b1 = tp.batch_at(0), tp.batch_at(0), tp.batch_at(1)
        np.testing.assert_array_equal(b0a["tokens"], b0b["tokens"])
        assert not np.array_equal(b0a["tokens"], b1["tokens"])
        # labels are next-token shifted
        np.testing.assert_array_equal(b0a["tokens"][:, 1:],
                                      b0a["labels"][:, :-1])

    def test_host_sharding_partition(self):
        from repro.data.pipeline import TokenPipeline
        full = TokenPipeline(vocab=50, seq_len=16, global_batch=8, seed=2)
        parts = [TokenPipeline(vocab=50, seq_len=16, global_batch=8, seed=2,
                               host_index=i, host_count=4) for i in range(4)]
        got = [p.batch_at(5)["tokens"] for p in parts]
        assert all(g.shape == (2, 16) for g in got)
        # different hosts draw different slices
        assert not np.array_equal(got[0], got[1])

    def test_prefetcher(self):
        from repro.data.pipeline import TokenPipeline, Prefetcher
        tp = TokenPipeline(vocab=50, seq_len=16, global_batch=2, seed=3)
        pf = Prefetcher(tp, start_step=7)
        step, batch = pf.next()
        assert step == 7
        np.testing.assert_array_equal(np.asarray(batch["tokens"]),
                                      tp.batch_at(7)["tokens"])
        pf.close()

    def test_skip_ahead_restart_semantics(self):
        """Restart at step k reproduces exactly the batches a continuous
        run would have seen (fault-tolerance invariant)."""
        from repro.data.pipeline import TokenPipeline
        tp = TokenPipeline(vocab=100, seq_len=8, global_batch=2, seed=4)
        run1 = [tp.batch_at(s)["tokens"] for s in range(10)]
        tp2 = TokenPipeline(vocab=100, seq_len=8, global_batch=2, seed=4)
        run2 = [tp2.batch_at(s)["tokens"] for s in range(5, 10)]
        for a, b in zip(run1[5:], run2):
            np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------
class TestCompression:
    def test_error_feedback_preserves_mean_signal(self):
        from repro.distributed import compress as C
        rng = np.random.default_rng(0)
        g_true = {"w": jnp.asarray(rng.normal(size=(256,)) * 1e-3)}
        err = C.init_error_state(g_true)
        acc = np.zeros(256)
        for _ in range(50):
            g, err = C.compress_grads(g_true, err)
            acc += np.asarray(g["w"])
        # accumulated compressed grads converge to accumulated true grads
        np.testing.assert_allclose(acc / 50, np.asarray(g_true["w"]),
                                   atol=2e-6)

    def test_compression_ratio(self):
        from repro.distributed import compress as C
        g = {"w": jnp.zeros((1000,)), "b": jnp.zeros((24,))}
        assert C.compressed_bytes(g) == 1024 + 8
        assert C.raw_bytes(g) == 4096


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------
class TestSharding:
    def test_param_specs_cover_all_archs(self):
        from repro.configs import ARCHS, get_config
        from repro.distributed import sharding as S
        from repro.launch.mesh import make_test_mesh
        from repro.models.lm import LM
        mesh = make_test_mesh((1, 1), ("data", "model"))
        for arch in ARCHS:
            cfg = get_config(arch)
            lm = LM(cfg)
            shapes = jax.eval_shape(lm.init, jax.random.PRNGKey(0))
            specs = S.param_specs(shapes, mesh, cfg)
            # every leaf got a PartitionSpec of the right rank
            def check(sd, sp):
                assert len(sp) <= len(sd.shape)
            jax.tree.map(check, shapes, specs)

    def test_divisibility_fallback(self):
        """Indivisible dims are replicated, not failed."""
        from repro.distributed.sharding import param_spec
        from repro.launch.mesh import make_test_mesh
        from jax.sharding import PartitionSpec as P
        mesh = make_test_mesh((1, 1), ("data", "model"))
        # prime dims can never shard over >1 axes; with 1x1 mesh they can
        spec = param_spec("groups/attn_0/attn/wq", (4, 7, 13), mesh)
        assert isinstance(spec, P)
