"""Per-arch smoke tests (reduced configs): one forward/train step on CPU,
asserting output shapes + no NaNs; serving-path consistency; QAT numerics
flow through every family."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get as get_cfg, list_archs, reduced
from repro.models import family_module
from repro.models.ssm_common import chunked_linear_attention, single_step

ARCHS = list_archs()


def _batch(cfg, key, b=2, s=32):
    tokens = jax.random.randint(key, (b, s), 0, cfg.vocab)
    labels = jax.random.randint(key, (b, s), 0, cfg.vocab)
    batch = {"tokens": tokens, "labels": labels}
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(key, (b, s, cfg.d_model),
                                            jnp.float32)
    if cfg.family == "vlm":
        batch["positions"] = jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32)[None, :, None], (b, s, 3))
    return batch


class TestFullConfigs:
    """The exact assigned hyperparameters are present (no allocation)."""

    EXPECT = {
        "qwen3-32b": dict(n_layers=64, d_model=5120, n_heads=64, kv_heads=8,
                          d_ff=25600, vocab=151936),
        "gemma3-1b": dict(n_layers=26, d_model=1152, n_heads=4, kv_heads=1,
                          d_ff=6912, vocab=262144),
        "gemma2-9b": dict(n_layers=42, d_model=3584, n_heads=16, kv_heads=8,
                          d_ff=14336, vocab=256000),
        "smollm-135m": dict(n_layers=30, d_model=576, n_heads=9, kv_heads=3,
                            d_ff=1536, vocab=49152),
        "phi3.5-moe-42b-a6.6b": dict(n_layers=32, d_model=4096, n_heads=32,
                                     kv_heads=8, moe_experts=16, moe_topk=2,
                                     vocab=32064),
        "deepseek-moe-16b": dict(n_layers=28, d_model=2048, n_heads=16,
                                 kv_heads=16, moe_experts=64, moe_topk=6,
                                 moe_shared=2, vocab=102400),
        "rwkv6-1.6b": dict(n_layers=24, d_model=2048, d_ff=7168,
                           vocab=65536),
        "qwen2-vl-72b": dict(n_layers=80, d_model=8192, n_heads=64,
                             kv_heads=8, d_ff=29568, vocab=152064),
        "whisper-medium": dict(d_model=1024, n_heads=16, kv_heads=16,
                               d_ff=4096, vocab=51865, enc_layers=24,
                               dec_layers=24),
        "zamba2-7b": dict(n_layers=81, d_model=3584, n_heads=32,
                          kv_heads=32, d_ff=14336, vocab=32000,
                          ssm_state=64),
        "deepseek-v3": dict(n_layers=61, d_model=7168, n_heads=128,
                            q_lora_rank=1536, kv_lora_rank=512,
                            qk_nope_head_dim=128, qk_rope_head_dim=64,
                            v_head_dim=128, first_dense=3,
                            dense_d_ff=18432, moe_experts=256, moe_topk=8,
                            moe_d_ff=2048, moe_shared=1, vocab=129280),
    }

    @pytest.mark.parametrize("arch", ARCHS)
    def test_exact_hparams(self, arch):
        cfg = get_cfg(arch)
        for k, v in self.EXPECT[arch].items():
            assert getattr(cfg, k) == v, (arch, k)

    def test_param_count_smollm(self):
        """SmolLM-135M full config: ~135M params (the end-to-end demo arch
        satisfies the ~100M training-driver requirement)."""
        cfg = get_cfg("smollm-135m")
        mod = family_module(cfg)
        shapes = jax.eval_shape(lambda k: mod.init_params(cfg, k),
                                jax.random.PRNGKey(0))
        n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
        assert 120e6 < n < 180e6

    def test_param_count_qwen2vl(self):
        cfg = get_cfg("qwen2-vl-72b")
        mod = family_module(cfg)
        shapes = jax.eval_shape(lambda k: mod.init_params(cfg, k),
                                jax.random.PRNGKey(0))
        n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
        assert 6.0e10 < n < 8.5e10


class TestSmoke:
    @pytest.mark.parametrize("arch", ARCHS)
    def test_train_step_shapes_no_nans(self, arch):
        cfg = reduced(arch)
        mod = family_module(cfg)
        key = jax.random.PRNGKey(0)
        params = mod.init_params(cfg, key)
        batch = _batch(cfg, key)
        loss, grads = jax.value_and_grad(mod.loss_fn)(params, batch, cfg)
        assert np.isfinite(float(loss))
        for g in jax.tree.leaves(grads):
            assert np.all(np.isfinite(np.asarray(g, np.float32)))

    @pytest.mark.parametrize("arch", ARCHS)
    def test_forward_shapes(self, arch):
        cfg = reduced(arch)
        mod = family_module(cfg)
        key = jax.random.PRNGKey(1)
        params = mod.init_params(cfg, key)
        b, s = 2, 16
        if cfg.family == "encdec":
            batch = _batch(cfg, key, b, s)
            from repro.models.encdec import encode
            enc = encode(params, batch["frames"], cfg)
            assert enc.shape == (b, s, cfg.d_model)
            return
        tokens = jax.random.randint(key, (b, s), 0, cfg.vocab)
        pos = (jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None, :, None],
                                (b, s, 3)) if cfg.family == "vlm" else None)
        logits = mod.forward(params, tokens, cfg, pos)
        assert logits.shape == (b, s, cfg.padded_vocab)
        assert np.all(np.isfinite(np.asarray(logits, np.float32)))

    @pytest.mark.parametrize("arch", ["qwen3-32b", "gemma2-9b",
                                      "deepseek-moe-16b", "zamba2-7b",
                                      "rwkv6-1.6b", "deepseek-v3"])
    def test_prefill_matches_forward(self, arch):
        cfg = reduced(arch)
        mod = family_module(cfg)
        key = jax.random.PRNGKey(2)
        params = mod.init_params(cfg, key)
        b, s = 2, 16
        tokens = jax.random.randint(key, (b, s), 0, cfg.vocab)
        cache = (mod.init_cache(cfg, b) if cfg.family == "ssm"
                 else mod.init_cache(cfg, b, 32, jnp.float32))
        logits, _ = mod.prefill(params, tokens, cfg, cache)
        full = mod.forward(params, tokens, cfg)
        np.testing.assert_allclose(np.asarray(logits[:, -1]),
                                   np.asarray(full[:, -1]),
                                   rtol=2e-3, atol=2e-3)

    @pytest.mark.parametrize("arch", ["qwen3-32b", "rwkv6-1.6b",
                                      "zamba2-7b"])
    def test_decode_steps_match_prefill(self, arch):
        """Greedy decode token-by-token == prefill of the same prefix."""
        cfg = reduced(arch)
        mod = family_module(cfg)
        key = jax.random.PRNGKey(3)
        params = mod.init_params(cfg, key)
        b, s = 1, 8
        tokens = jax.random.randint(key, (b, s + 4), 0, cfg.vocab)
        cache = (mod.init_cache(cfg, b) if cfg.family == "ssm"
                 else mod.init_cache(cfg, b, 32, jnp.float32))
        _, cache = mod.prefill(params, tokens[:, :s], cfg, cache)
        outs = []
        for t in range(4):
            lg, cache = mod.decode_step(params, tokens[:, s + t:s + t + 1],
                                        cfg, cache)
            outs.append(lg[:, 0])
        ref = mod.forward(params, tokens, cfg)
        got = jnp.stack(outs, 1)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(ref[:, s:s + 4]),
                                   rtol=5e-3, atol=5e-3)

    @pytest.mark.parametrize("pe", ["int16", "lightpe1", "lightpe2", "int8"])
    def test_qat_numerics_train(self, pe):
        """QAT runs through a full train step for every PE type."""
        cfg = reduced("smollm-135m").replace(pe_type=pe)
        mod = family_module(cfg)
        key = jax.random.PRNGKey(4)
        params = mod.init_params(cfg, key)
        batch = _batch(cfg, key)
        loss, grads = jax.value_and_grad(mod.loss_fn)(params, batch, cfg)
        assert np.isfinite(float(loss))
        gnorm = float(jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                                   for g in jax.tree.leaves(grads))))
        assert np.isfinite(gnorm) and gnorm > 0


class TestLatentAttention:
    """DeepSeek-V3's multi-head latent attention: the naive form
    (training, forward, prefill) and the absorbed form (decode over the
    latent cache) are one product."""

    @staticmethod
    def _cfg():
        # float32 activations; capacity for every (token, expert) pair so
        # that no routing drop separates a prefill from the full forward
        cfg = reduced("deepseek-v3")
        return cfg.replace(dtype="float32", capacity_factor=float(
            cfg.moe_experts) / cfg.moe_topk)

    def test_cache_holds_only_the_latent(self):
        cfg = get_cfg("deepseek-v3")
        mod = family_module(cfg)
        cache = jax.eval_shape(lambda: mod.init_cache(cfg, 1, 8))
        leaves = cache["scan"]
        assert set(leaves) == {"c_kv", "k_rope", "index"}
        assert leaves["c_kv"].shape[-1] + leaves["k_rope"].shape[-1] == 576
        assert len(cache["dense"]) == cfg.first_dense

    def test_decode_through_latent_cache_matches_forward(self):
        """Prefill a prompt, then decode four tokens through the latent
        cache (absorbed form): each step's logits equal the full
        forward's (naive form) at that position."""
        cfg = self._cfg()
        mod = family_module(cfg)
        b, s, steps = 2, 8, 4
        with jax.default_matmul_precision("highest"):
            params = mod.init_params(cfg, jax.random.PRNGKey(5))
            tokens = jax.random.randint(jax.random.PRNGKey(6),
                                        (b, s + steps), 0, cfg.vocab)
            cache = mod.init_cache(cfg, b, 16, jnp.float32)
            first, cache = mod.prefill(params, tokens[:, :s], cfg, cache)
            outs = [first[:, 0]]
            for t in range(steps):
                lg, cache = mod.decode_step(
                    params, tokens[:, s + t:s + t + 1], cfg, cache)
                outs.append(lg[:, 0])
            ref = mod.forward(params, tokens, cfg)
        # float32 at full matmul precision: the two forms and the padded
        # cache only reorder sums, a few ulps through three layers
        # (measured 2.6e-6 on logits of magnitude ~4); bfloat16 would
        # miss by ~1e-2
        np.testing.assert_allclose(np.asarray(jnp.stack(outs, 1)),
                                   np.asarray(ref[:, s - 1:s + steps]),
                                   rtol=1e-5, atol=2e-5)

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_absorbed_equals_naive(self, seed):
        """On seeded random weights, the absorbed form over a latent cache
        equals the naive (decompressed) form over the same cache; the
        absorbed form computed in bfloat16 does not."""
        from repro.models import mla as MLA
        from repro.quant.qconfig import preset
        cfg, q = self._cfg(), preset("fp32")
        k_p, k_x = jax.random.split(jax.random.PRNGKey(seed))
        b, s = 2, 12
        pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        with jax.default_matmul_precision("highest"):
            p = MLA.mla_init(k_p, cfg)
            x = jax.random.normal(k_x, (b, s, cfg.d_model), jnp.float32)

            def run(p, x, dtype, absorb):
                cache = MLA.make_mla_cache(b, 16, cfg, dtype)
                out, _ = MLA.mla_attention(p, x, cfg, q, pos, cache,
                                           absorb=absorb)
                return np.asarray(out, np.float64)
            naive = run(p, x, jnp.float32, False)
            absorbed = run(p, x, jnp.float32, True)
            bf16 = run(jax.tree.map(lambda a: a.astype(jnp.bfloat16), p),
                       x.astype(jnp.bfloat16), jnp.bfloat16, True)
        # float32 reassociation of sums over the latent width and the
        # cache (measured <= 6e-7 on outputs of magnitude ~3); bfloat16
        # rounds each operand to 2**-8 relative (measured ~1.5e-2)
        tol = dict(rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(absorbed, naive, **tol)
        with pytest.raises(AssertionError):
            np.testing.assert_allclose(bf16, naive, **tol)


class TestSSMCommon:
    def test_chunked_matches_naive(self, rng):
        b, s, h, dk, dv = 2, 32, 2, 4, 4
        r = jnp.asarray(rng.normal(size=(b, s, h, dk)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(b, s, h, dk)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(b, s, h, dv)), jnp.float32)
        lw = jnp.asarray(-np.abs(rng.normal(size=(b, s, h, dk))), jnp.float32)
        u = jnp.asarray(rng.normal(size=(h, dk)), jnp.float32)
        o16, s16 = chunked_linear_attention(r, k, v, lw, u, chunk=16)
        o8, s8 = chunked_linear_attention(r, k, v, lw, u, chunk=8)
        np.testing.assert_allclose(o16, o8, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(s16, s8, rtol=1e-4, atol=1e-4)

    def test_state_carries_across_calls(self, rng):
        """prefill(x[:16]) then prefill(x[16:]) == prefill(x) (streaming)."""
        b, s, h, dk, dv = 1, 32, 2, 4, 4
        args = [jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
                for d in (dk, dk, dv)]
        lw = jnp.asarray(-np.abs(rng.normal(size=(b, s, h, dk))),
                         jnp.float32)
        u = jnp.asarray(rng.normal(size=(h, dk)), jnp.float32)
        o_full, s_full = chunked_linear_attention(*args, lw, u, chunk=16)
        o1, s1 = chunked_linear_attention(*[a[:, :16] for a in args],
                                          lw[:, :16], u, chunk=16)
        o2, s2 = chunked_linear_attention(*[a[:, 16:] for a in args],
                                          lw[:, 16:], u, chunk=16,
                                          initial_state=s1)
        np.testing.assert_allclose(jnp.concatenate([o1, o2], 1), o_full,
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(s2, s_full, rtol=1e-4, atol=1e-4)
