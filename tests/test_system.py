"""End-to-end behaviour: training converges, checkpoints restart exactly,
elastic restore works, QAT accuracy matches the paper's story, serving
engine generates, gradient compression preserves training."""

import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import latest_step
from repro.configs import reduced
from repro.data import lm_pipeline
from repro.data.synthetic import eval_image_set, image_batch, token_batch
from repro.launch.mesh import make_mesh
from repro.models import cnn, family_module
from repro.optim import adamw, paper_step_decay, sgd_nesterov, warmup_cosine
from repro.serve import ServeEngine, dequantize_params, quantize_params
from repro.train import fit, init_state, make_train_step, resume


@pytest.fixture
def tmp_ckpt(tmp_path):
    d = str(tmp_path / "ckpt")
    yield d
    shutil.rmtree(d, ignore_errors=True)


class TestTraining:
    def test_loss_decreases(self):
        cfg = reduced("smollm-135m")
        mod = family_module(cfg)
        opt = adamw(warmup_cosine(2e-3, 10, 300))
        state = init_state(cfg, mod, opt, jax.random.PRNGKey(0))
        step = jax.jit(make_train_step(cfg, mod, opt, n_micro=2),
                       donate_argnums=0)
        pipe = lm_pipeline(cfg, global_batch=8, seq=64)
        losses = []
        for _ in range(60):
            state, m = step(state, next(pipe))
            losses.append(float(m["loss"]))
        assert np.mean(losses[-10:]) < np.mean(losses[:10]) - 0.3

    def test_microbatching_equivalent(self):
        """n_micro=1 and n_micro=4 give the same update (mean grads)."""
        cfg = reduced("smollm-135m")
        mod = family_module(cfg)
        opt = adamw(warmup_cosine(1e-3, 1, 100))
        s1 = init_state(cfg, mod, opt, jax.random.PRNGKey(0))
        s4 = init_state(cfg, mod, opt, jax.random.PRNGKey(0))
        pipe = lm_pipeline(cfg, global_batch=8, seq=32)
        batch = next(pipe)
        f1 = jax.jit(make_train_step(cfg, mod, opt, n_micro=1))
        f4 = jax.jit(make_train_step(cfg, mod, opt, n_micro=4))
        s1, m1 = f1(s1, batch)
        s4, m4 = f4(s4, batch)
        d = max(float(jnp.max(jnp.abs(a - b))) for a, b in
                zip(jax.tree.leaves(s1.params), jax.tree.leaves(s4.params)))
        assert d < 5e-5
        assert float(m1["loss"]) == pytest.approx(float(m4["loss"]),
                                                  rel=1e-3)


class TestFaultTolerance:
    def test_checkpoint_restart_exact(self, tmp_ckpt):
        cfg = reduced("smollm-135m")
        mod = family_module(cfg)
        opt = adamw(warmup_cosine(1e-3, 5, 100))
        step = jax.jit(make_train_step(cfg, mod, opt, n_micro=1))
        mesh = make_mesh((1, 1), ("data", "model"))

        state_a = init_state(cfg, mod, opt, jax.random.PRNGKey(0))
        pipe_a = lm_pipeline(cfg, global_batch=4, seq=32)
        state_a = fit(state_a, step, pipe_a, 10, log_fn=lambda s: None)

        state_b = init_state(cfg, mod, opt, jax.random.PRNGKey(0))
        pipe_b = lm_pipeline(cfg, global_batch=4, seq=32)
        state_b = fit(state_b, step, pipe_b, 5, ckpt_dir=tmp_ckpt,
                      ckpt_every=5, log_fn=lambda s: None)
        del state_b  # crash
        pipe_b2 = lm_pipeline(cfg, global_batch=4, seq=32)
        state_b2 = resume(cfg, mod, opt, mesh, tmp_ckpt, pipe_b2)
        assert int(state_b2.step) == 5 and pipe_b2.state.step == 5
        state_b2 = fit(state_b2, step, pipe_b2, 10, log_fn=lambda s: None)

        for a, b in zip(jax.tree.leaves(state_a.params),
                        jax.tree.leaves(state_b2.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6)

    def test_elastic_restore_changes_mesh(self, tmp_ckpt):
        from repro.checkpoint import manager as ckpt
        from repro.launch.sharding import make_param_shardings
        cfg = reduced("qwen3-32b")
        mod = family_module(cfg)
        params = mod.init_params(cfg, jax.random.PRNGKey(0))
        ckpt.save(tmp_ckpt, 1, params)
        mesh = make_mesh((1, 1), ("data", "model"))
        shardings = make_param_shardings(
            cfg, jax.eval_shape(lambda: params), mesh, "train")
        restored, _, _ = ckpt.restore(tmp_ckpt, 1, params,
                                      shardings=shardings)
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b))

    def test_keep_k_gc(self, tmp_ckpt):
        from repro.checkpoint import manager as ckpt
        from repro.checkpoint.manager import all_steps
        params = {"w": jnp.zeros((4,))}
        for s in range(1, 6):
            ckpt.save(tmp_ckpt, s, params, keep=2)
        assert latest_step(tmp_ckpt) == 5
        assert all_steps(tmp_ckpt) == [4, 5]

    def test_pipeline_deterministic_restart(self):
        b1 = token_batch(0, 7, 4, 16, 100)
        b2 = token_batch(0, 7, 4, 16, 100)
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])


class TestGradCompression:
    def test_int8_error_feedback_single_shard(self):
        from jax.experimental.shard_map import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.optim.grad_compress import compressed_psum_mean
        mesh = make_mesh((1,), ("data",))
        g = jnp.asarray(np.random.default_rng(0).normal(size=(64,)),
                        jnp.float32)
        err = jnp.zeros_like(g)
        f = shard_map(lambda a, b: compressed_psum_mean(a, b, ("data",), 1),
                      mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()))
        mean, new_err = f(g, err)
        scale = float(jnp.max(jnp.abs(g))) / 127.0
        assert float(jnp.max(jnp.abs(mean - g))) <= scale / 2 + 1e-6
        np.testing.assert_allclose(np.asarray(new_err),
                                   np.asarray(g - mean), atol=1e-6)


class TestQATAccuracy:
    @pytest.mark.slow
    def test_paper_accuracy_ordering_resnet(self):
        """Figs. 5-6: all PE types learn; LightPE within a few points of
        FP32 ('on par')."""
        accs = {}
        for pe in ("fp32", "int16", "lightpe1"):
            key = jax.random.PRNGKey(0)
            params = cnn.resnet_init(key, depth=8, n_classes=10)
            opt = sgd_nesterov(paper_step_decay(0.02, 60), weight_decay=5e-4)
            ostate = opt.init(params)

            @jax.jit
            def step(params, ostate, batch, pe=pe):
                (loss, acc), grads = jax.value_and_grad(
                    lambda p: cnn.cnn_loss(cnn.resnet_apply, p, batch, pe),
                    has_aux=True)(params)
                params, ostate = opt.update(grads, ostate, params)
                return params, ostate, loss, acc

            for i in range(180):
                params, ostate, loss, acc = step(
                    params, ostate, image_batch(0, i, 64, 10))
            ev = eval_image_set(0, 256, 10)
            logits = cnn.resnet_apply(params, ev["images"], pe)
            accs[pe] = float(jnp.mean(
                (jnp.argmax(logits, -1) == ev["labels"]).astype(jnp.float32)))
        # 'on par': LightPE within a few points of FP32 in either
        # direction (quantization sometimes regularizes on small tasks)
        assert abs(accs["fp32"] - accs["lightpe1"]) <= 0.1
        assert abs(accs["int16"] - accs["lightpe1"]) <= 0.1
        assert min(accs.values()) > 0.5  # all PE types actually learn


class TestServing:
    def test_engine_generates_and_frees_slots(self):
        cfg = reduced("smollm-135m")
        mod = family_module(cfg)
        params = mod.init_params(cfg, jax.random.PRNGKey(0))
        eng = ServeEngine(cfg, mod, params, batch_slots=2, max_len=64)
        reqs = [eng.submit(np.arange(4) % cfg.vocab, max_new=3)
                for _ in range(4)]  # more requests than slots
        eng.run()
        assert all(r.done and len(r.out) == 3 for r in reqs)

    def test_quantized_weights_close_logits(self):
        cfg = reduced("smollm-135m")
        mod = family_module(cfg)
        params = mod.init_params(cfg, jax.random.PRNGKey(0))
        qp = dequantize_params(quantize_params(params, "int8",
                                               min_size=1 << 8))
        tokens = jnp.arange(8)[None] % cfg.vocab
        a = mod.forward(params, tokens, cfg)
        b = mod.forward(qp, tokens, cfg)
        rel = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(a))
        assert rel < 0.25
