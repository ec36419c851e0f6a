"""KV-cache incremental decoding (reference vllm_backend analogue):
correctness vs the full forward, ring-buffer wrap, GQA, speed, and the
LM PPO experience path.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.models import llama_init
from dlrover_tpu.models.llama import LlamaConfig, llama_apply
from dlrover_tpu.rl.generation import (
    GenerateConfig,
    KVCacheGenerationBackend,
    decode_step,
    generate,
    init_kv_cache,
    prefill,
)


def tiny_config(**kw):
    d = dict(
        vocab_size=64, dim=32, n_layers=2, n_heads=4, n_kv_heads=2,
        mlp_dim=64, max_seq_len=128, attn_impl="reference", remat=False,
        dtype="float32",
    )
    d.update(kw)
    return LlamaConfig(**d)


class TestDecodeMatchesFullForward:
    def test_prefill_logits_match(self):
        config = tiny_config()
        params = llama_init(config, jax.random.key(0))
        tokens = jax.random.randint(jax.random.key(1), (2, 9), 0, 64)
        cache = init_kv_cache(config, 2, 32)
        logits, cache = prefill(config, params, tokens, cache)
        full = llama_apply(config, params, tokens)
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(full[:, -1]), atol=2e-4
        )

    def test_decode_steps_match(self):
        config = tiny_config()
        params = llama_init(config, jax.random.key(0))
        tokens = np.asarray(
            jax.random.randint(jax.random.key(1), (2, 6), 0, 64)
        )
        cache = init_kv_cache(config, 2, 32)
        _, cache = prefill(config, params, jnp.asarray(tokens), cache)
        # feed 3 more tokens one at a time; logits at each step must
        # equal a fresh full forward over the growing prefix
        prefix = tokens
        for step in range(3):
            nxt = np.asarray(
                jax.random.randint(jax.random.key(10 + step), (2,), 0, 64)
            )
            logits, cache = decode_step(
                config, params, jnp.asarray(nxt), prefix.shape[1], cache
            )
            prefix = np.concatenate([prefix, nxt[:, None]], axis=1)
            full = llama_apply(config, params, jnp.asarray(prefix))
            np.testing.assert_allclose(
                np.asarray(logits), np.asarray(full[:, -1]), atol=3e-4,
                err_msg=f"step {step}",
            )

    def test_greedy_generate_matches_full_forward_loop(self):
        config = tiny_config()
        params = llama_init(config, jax.random.key(0))
        prompt = jax.random.randint(jax.random.key(1), (2, 5), 0, 64)
        res = generate(
            config, params, prompt, jax.random.key(2),
            GenerateConfig(max_new_tokens=6, temperature=0.0),
        )
        # reference: argmax with a full forward per step
        seq = np.asarray(prompt)
        for _ in range(6):
            logits = llama_apply(config, params, jnp.asarray(seq))
            nxt = np.asarray(jnp.argmax(logits[:, -1], -1))
            seq = np.concatenate([seq, nxt[:, None]], axis=1)
        np.testing.assert_array_equal(np.asarray(res.sequences), seq)

    def test_gqa_heads(self):
        config = tiny_config(n_heads=8, n_kv_heads=2)
        params = llama_init(config, jax.random.key(0))
        tokens = jax.random.randint(jax.random.key(1), (2, 7), 0, 64)
        cache = init_kv_cache(config, 2, 16)
        logits, _ = prefill(config, params, tokens, cache)
        full = llama_apply(config, params, tokens)
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(full[:, -1]), atol=2e-4
        )


class TestRingBuffer:
    def test_wraps_past_capacity(self):
        """capacity < prompt+new: generation proceeds with a sliding
        window (old slots overwritten, attention over the window)."""
        config = tiny_config()
        params = llama_init(config, jax.random.key(0))
        prompt = jax.random.randint(jax.random.key(1), (2, 4), 0, 64)
        res = generate(
            config, params, prompt, jax.random.key(2),
            GenerateConfig(max_new_tokens=10, cache_capacity=8,
                           temperature=0.7),
        )
        assert res.sequences.shape == (2, 14)
        assert np.isfinite(np.asarray(res.logprobs)).all()

    def test_window_attends_recent_only(self):
        """After wrap, every slot position must be within the window."""
        config = tiny_config()
        cache = init_kv_cache(config, 1, 4)
        params = llama_init(config, jax.random.key(0))
        _, cache = prefill(
            config, params,
            jax.random.randint(jax.random.key(1), (1, 3), 0, 64), cache,
        )
        for pos in range(3, 9):
            tok = jnp.asarray([int(pos % 60)])
            _, cache = decode_step(config, params, tok, pos, cache)
        pos_buf = np.asarray(cache.pos)
        assert pos_buf.min() >= 9 - 4  # only the last window retained


class TestEosMask:
    def test_mask_stops_after_eos(self):
        config = tiny_config()
        params = llama_init(config, jax.random.key(0))
        prompt = jax.random.randint(jax.random.key(1), (1, 4), 0, 64)
        res = generate(
            config, params, prompt, jax.random.key(2),
            GenerateConfig(max_new_tokens=8, temperature=1.0, eos_id=0),
        )
        toks = np.asarray(res.sequences)[0, 4:]
        mask = np.asarray(res.mask)[0]
        if (toks == 0).any():
            first = int(np.argmax(toks == 0))
            assert mask[: first + 1].all()
            assert not mask[first + 1:].any()
        else:
            assert mask.all()


class TestSpeed:
    def test_incremental_beats_full_forward(self):
        """The point of the backend: O(T) per token instead of O(T^2).
        Even on CPU at toy scale the win is large for enough steps."""
        config = tiny_config(n_layers=4, dim=64, max_seq_len=512)
        params = llama_init(config, jax.random.key(0))
        prompt = jax.random.randint(jax.random.key(1), (4, 32), 0, 64)
        N = 64
        backend = KVCacheGenerationBackend(
            config, GenerateConfig(max_new_tokens=N, temperature=0.0)
        )
        res = backend.generate(params, prompt, jax.random.key(2))
        jax.block_until_ready(res.sequences)  # compile
        t0 = time.perf_counter()
        res = backend.generate(params, prompt, jax.random.key(3))
        jax.block_until_ready(res.sequences)
        inc_s = time.perf_counter() - t0

        # full-forward-per-token baseline (what make_experience used to
        # imply): jitted once per (growing) shape — time steady-state
        # re-decode at final length only, scaled by N (flatters it)
        seq = res.sequences

        @jax.jit
        def full(params, seq):
            return llama_apply(config, params, seq)

        jax.block_until_ready(full(params, seq))
        t0 = time.perf_counter()
        jax.block_until_ready(full(params, seq))
        full_s = (time.perf_counter() - t0) * N

        assert inc_s < full_s, (inc_s, full_s)
        tok_s = 4 * N / inc_s
        print(f"incremental {tok_s:.0f} tok/s vs full-forward x{N}: "
              f"{4 * N / full_s:.0f} tok/s")


class TestRewardPlacement:
    def test_score_lands_on_last_valid_token_with_prompt_mask(self):
        """LM masks are zero over the prompt; the sequence score must
        land on the last *positionally* valid token, not at index
        sum(mask)-1 (which is inside the masked prompt region)."""
        from dlrover_tpu.rl.ppo_utils import rewards_with_kl

        B, T, P = 2, 8, 5
        mask = np.zeros((B, T), np.float32)
        mask[:, P - 1:] = 1.0          # 4 valid positions: 4,5,6,7
        mask[1, 6:] = 0.0              # row 1 terminated early
        lp = jnp.zeros((B, T))
        scores = jnp.asarray([1.0, 2.0])
        r = np.asarray(rewards_with_kl(
            scores, lp, lp, jnp.asarray(mask), kl_coef=0.0
        ))
        assert r[0, 7] == 1.0 and r[0, :7].sum() == 0.0
        assert r[1, 5] == 2.0 and (np.delete(r[1], 5) == 0).all()

    def test_lm_ppo_advantages_carry_reward(self):
        """End-to-end: a nonzero sequence score must produce nonzero
        advantages in the buffer (regression: the count-based index
        dropped the reward entirely)."""
        from dlrover_tpu.rl import (
            LMPPOTrainer,
            ModelEngine,
            ModelSpec,
            PPOConfig,
        )

        config = tiny_config()
        engine = ModelEngine({
            "actor": ModelSpec(
                init_fn=lambda rng: llama_init(config, rng),
                apply_fn=lambda p, t: llama_apply(config, p, t),
                trainable=True, optimizer=optax.adam(1e-4),
            ),
            "critic": ModelSpec(
                init_fn=lambda rng: {
                    "emb": jax.random.normal(
                        rng, (config.vocab_size,)) * 0.0,
                },
                apply_fn=lambda p, t: p["emb"][t],
                trainable=True, optimizer=optax.adam(1e-3),
            ),
        })
        trainer = LMPPOTrainer(
            engine, PPOConfig(whiten_advantages=False, kl_coef=0.0),
            llama_config=config,
            score_fn=lambda seq, m: np.ones(seq.shape[0]),
            gen=GenerateConfig(max_new_tokens=4, temperature=1.0),
        )
        prompts = {"tokens": np.asarray(
            jax.random.randint(jax.random.key(5), (2, 5), 0, 64)
        )}
        trainer.make_experience(prompts)
        adv = np.stack([
            np.asarray(s["advantages"]) for s in trainer.buffer._samples
        ])
        assert np.abs(adv).max() > 0.1, (
            "sequence reward did not reach the advantages"
        )


class TestLMPPO:
    def test_lm_ppo_iteration(self):
        from dlrover_tpu.rl import (
            LMPPOTrainer,
            ModelEngine,
            ModelSpec,
            PPOConfig,
        )

        config = tiny_config()

        def actor_apply(params, tokens):
            return llama_apply(config, params, tokens)

        def critic_init(rng):
            return {"w": jax.random.normal(rng, (config.dim, 1)) * 0.02,
                    "emb": jax.random.normal(
                        rng, (config.vocab_size, config.dim)) * 0.02}

        def critic_apply(params, tokens):
            h = params["emb"][tokens]
            return (h @ params["w"])[..., 0]

        engine = ModelEngine({
            "actor": ModelSpec(
                init_fn=lambda rng: llama_init(config, rng),
                apply_fn=actor_apply, trainable=True,
                optimizer=optax.adam(1e-4),
            ),
            "critic": ModelSpec(
                init_fn=critic_init, apply_fn=critic_apply,
                trainable=True, optimizer=optax.adam(1e-3),
            ),
        })

        def score_fn(sequences, gen_mask):
            # toy reward: fraction of even tokens in the continuation
            gen = np.asarray(sequences)[:, -gen_mask.shape[1]:]
            return (np.asarray(gen) % 2 == 0).mean(axis=1)

        trainer = LMPPOTrainer(
            engine, PPOConfig(ppo_epochs=2, train_batch_size=4),
            llama_config=config, score_fn=score_fn,
            gen=GenerateConfig(max_new_tokens=6, temperature=1.0),
        )
        prompts = {"tokens": np.asarray(
            jax.random.randint(jax.random.key(5), (4, 5), 0, 64)
        )}
        stats = trainer.train([prompts], iterations=1)
        assert stats, "no update stats"
        assert np.isfinite(float(stats["policy_loss"]))
        assert np.isfinite(float(stats["value_loss"]))


class TestMoEDecode:
    """MoE policies decode through the same KV-cache path (an earlier review's item:
    rl/generation previously raised NotImplementedError for MoE)."""

    def _moe_config(self):
        return tiny_config(n_experts=4, moe_top_k=2, mlp_dim=32)

    def test_prefill_logits_match_full_forward(self):
        config = self._moe_config()
        params = llama_init(config, jax.random.key(0))
        tokens = jax.random.randint(jax.random.key(1), (2, 9), 0, 64)
        cache = init_kv_cache(config, 2, 32)
        logits, _ = prefill(config, params, tokens, cache)
        full = llama_apply(config, params, tokens)
        # training moe_ffn enforces per-expert capacity (tokens can be
        # dropped); decode computes the exact top-k mixture, so allow a
        # loose tolerance driven by capacity-dropping differences only
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(full[:, -1]), atol=0.35
        )

    def test_generate_finite_and_reproducible(self):
        config = self._moe_config()
        params = llama_init(config, jax.random.key(0))
        prompts = jax.random.randint(jax.random.key(1), (2, 5), 0, 64)
        out = generate(config, params, prompts, jax.random.key(2),
                       GenerateConfig(max_new_tokens=6))
        assert out.sequences.shape == (2, 11)
        assert np.isfinite(np.asarray(out.logprobs)).all()
        out2 = generate(config, params, prompts, jax.random.key(2),
                        GenerateConfig(max_new_tokens=6))
        np.testing.assert_array_equal(
            np.asarray(out.sequences), np.asarray(out2.sequences))

    def test_backend_accepts_moe(self):
        config = self._moe_config()
        params = llama_init(config, jax.random.key(0))
        backend = KVCacheGenerationBackend(
            config, GenerateConfig(max_new_tokens=4))
        out = backend.generate(params, np.zeros((1, 3), np.int32),
                               jax.random.key(0))
        assert out.sequences.shape == (1, 7)


class TestPromptBuckets:
    """Backend prompt-length bucketing: one trace per power-of-two
    bucket (the PR 11 cache-miss assertion idiom applied to jit
    retraces), with bit-exact greedy parity against the unpadded
    path."""

    def test_one_trace_serves_every_length_in_a_bucket(self):
        config = tiny_config()
        params = llama_init(config, jax.random.key(0))
        backend = KVCacheGenerationBackend(
            config, GenerateConfig(max_new_tokens=4, temperature=0.0)
        )
        for P in (3, 5, 6, 7, 8):
            prompt = jax.random.randint(
                jax.random.key(P), (2, P), 0, 64
            )
            res = backend.generate(params, prompt, jax.random.key(2))
            assert res.sequences.shape == (2, P + 4)
        # the cache-miss assertion: five prompt lengths, ONE compile
        assert backend.trace_count() == 1
        # crossing the bucket boundary costs exactly one more
        backend.generate(
            params,
            jax.random.randint(jax.random.key(9), (2, 9), 0, 64),
            jax.random.key(2),
        )
        assert backend.trace_count() == 2

    def test_bucketed_greedy_matches_unbucketed(self):
        config = tiny_config()
        params = llama_init(config, jax.random.key(0))
        gen = GenerateConfig(max_new_tokens=6, temperature=0.0)
        bucketed = KVCacheGenerationBackend(config, gen)
        exact = KVCacheGenerationBackend(
            config, gen, bucket_prompts=False
        )
        for P in (3, 6, 11):
            prompt = jax.random.randint(
                jax.random.key(P), (2, P), 0, 64
            )
            a = bucketed.generate(params, prompt, jax.random.key(4))
            b = exact.generate(params, prompt, jax.random.key(4))
            np.testing.assert_array_equal(
                np.asarray(a.sequences), np.asarray(b.sequences),
                err_msg=f"P={P}",
            )

    def test_bucketed_matches_full_forward_greedy(self):
        """Pads can never be attended: the padded-bucket continuation
        equals the non-cached full forward over the REAL prompt."""
        config = tiny_config(n_heads=8, n_kv_heads=2)  # GQA grouping
        params = llama_init(config, jax.random.key(0))
        backend = KVCacheGenerationBackend(
            config, GenerateConfig(max_new_tokens=5, temperature=0.0)
        )
        prompt = jax.random.randint(jax.random.key(1), (2, 5), 0, 64)
        res = backend.generate(params, prompt, jax.random.key(2))
        seq = np.asarray(prompt)
        for _ in range(5):
            logits = llama_apply(config, params, jnp.asarray(seq))
            nxt = np.asarray(jnp.argmax(logits[:, -1], -1))
            seq = np.concatenate([seq, nxt[:, None]], axis=1)
        np.testing.assert_array_equal(np.asarray(res.sequences), seq)

    def test_explicit_small_cache_falls_back_to_sliding_window(self):
        """A cache smaller than the bucket is the static truncation
        path — bucketing must step aside, not mis-mask."""
        config = tiny_config()
        params = llama_init(config, jax.random.key(0))
        backend = KVCacheGenerationBackend(
            config,
            GenerateConfig(
                max_new_tokens=4, temperature=0.7, cache_capacity=6
            ),
        )
        prompt = jax.random.randint(jax.random.key(1), (2, 10), 0, 64)
        res = backend.generate(params, prompt, jax.random.key(2))
        assert res.sequences.shape == (2, 14)
        assert np.isfinite(np.asarray(res.logprobs)).all()

    def test_sampling_deterministic_across_bucket_padding(self):
        """Temperature sampling under a fixed key is a pure function
        of (params, prompt, key) — the pad width must not leak into
        the draws (same bucket, different real lengths)."""
        config = tiny_config()
        params = llama_init(config, jax.random.key(0))
        backend = KVCacheGenerationBackend(
            config, GenerateConfig(max_new_tokens=6, temperature=1.0)
        )
        prompt = jax.random.randint(jax.random.key(1), (2, 5), 0, 64)
        a = backend.generate(params, prompt, jax.random.key(3))
        b = backend.generate(params, prompt, jax.random.key(3))
        np.testing.assert_array_equal(
            np.asarray(a.sequences), np.asarray(b.sequences)
        )
        c = backend.generate(params, prompt, jax.random.key(4))
        assert not np.array_equal(
            np.asarray(a.sequences), np.asarray(c.sequences)
        )


class TestDecodeGqaAndWrap:
    """Decode-path seams the serving scheduler sits on: GQA head-group
    indexing during INCREMENTAL decode (not just prefill) and ring
    wraparound past the configured window."""

    def test_gqa_decode_steps_match_full_forward(self):
        config = tiny_config(n_heads=8, n_kv_heads=2)
        params = llama_init(config, jax.random.key(0))
        tokens = np.asarray(
            jax.random.randint(jax.random.key(1), (2, 6), 0, 64)
        )
        cache = init_kv_cache(config, 2, 32)
        _, cache = prefill(config, params, jnp.asarray(tokens), cache)
        prefix = tokens
        for step in range(4):
            nxt = np.asarray(jax.random.randint(
                jax.random.key(30 + step), (2,), 0, 64
            ))
            logits, cache = decode_step(
                config, params, jnp.asarray(nxt), prefix.shape[1],
                cache,
            )
            prefix = np.concatenate([prefix, nxt[:, None]], axis=1)
            full = llama_apply(config, params, jnp.asarray(prefix))
            np.testing.assert_allclose(
                np.asarray(logits), np.asarray(full[:, -1]),
                atol=3e-4, err_msg=f"gqa decode step {step}",
            )

    def test_wraparound_matches_windowed_full_forward(self):
        """Past capacity the ring holds exactly the newest C tokens:
        decode logits must match a full forward over that window."""
        config = tiny_config()
        params = llama_init(config, jax.random.key(0))
        C = 8
        cache = init_kv_cache(config, 1, C)
        toks = np.asarray(jax.random.randint(
            jax.random.key(2), (1, 20), 0, 64
        ))
        _, cache = prefill(config, params, jnp.asarray(toks[:, :6]),
                           cache)
        for pos in range(6, 14):  # decode well past C
            logits, cache = decode_step(
                config, params, jnp.asarray(toks[:, pos]), pos, cache
            )
        # the window now holds positions [14-C, 13] = [6, 13]; one more
        # step must equal a fresh forward over exactly that window
        window = toks[:, 14 - C:14]
        # consume token 14 against the window: positions inside the
        # ring are absolute, so compare via the windowed forward's
        # last-token logits after appending the same token
        logits, cache = decode_step(
            config, params, jnp.asarray(toks[:, 14]), 14, cache
        )
        ref_in = np.concatenate([window, toks[:, 14:15]], axis=1)
        full = llama_apply(config, params, jnp.asarray(ref_in))
        # rope positions differ (absolute vs window-relative), so the
        # assertion is structural: finite logits and a fully-advanced
        # window
        assert np.isfinite(np.asarray(logits)).all()
        pos_buf = np.sort(np.asarray(cache.pos))
        np.testing.assert_array_equal(pos_buf, np.arange(7, 15))
        assert np.isfinite(np.asarray(full)).all()


class TestPrefillLongerThanCache:
    def test_keeps_last_window(self):
        """P > C prompts keep the last C tokens (unique ring slots; a
        single duplicate-index scatter has undefined winners)."""
        config = tiny_config()
        params = llama_init(config, jax.random.key(0))
        tokens = jax.random.randint(jax.random.key(1), (2, 20), 0, 64)
        C = 8
        cache = init_kv_cache(config, 2, C)
        logits, cache = prefill(config, params, tokens, cache)
        # every cache slot must hold one of the LAST C positions
        pos = np.sort(np.asarray(cache.pos))
        np.testing.assert_array_equal(pos, np.arange(12, 20))
        assert np.isfinite(np.asarray(logits)).all()


def test_first_token_rng_independent_of_scan_draws():
    """Token 0 must use a split key, not the scan carry's ancestor."""
    config = tiny_config()
    params = llama_init(config, jax.random.key(0))
    prompts = jax.random.randint(jax.random.key(1), (4, 5), 0, 64)
    out = generate(config, params, prompts, jax.random.key(7),
                   GenerateConfig(max_new_tokens=8, temperature=1.0))
    # smoke: finite + deterministic under the same key
    out2 = generate(config, params, prompts, jax.random.key(7),
                    GenerateConfig(max_new_tokens=8, temperature=1.0))
    np.testing.assert_array_equal(
        np.asarray(out.sequences), np.asarray(out2.sequences))


def test_lm_ppo_iteration_moe_policy():
    """PPO e2e with an MoE policy through the KV-cache backend."""
    from dlrover_tpu.rl import (
        LMPPOTrainer,
        ModelEngine,
        ModelSpec,
        PPOConfig,
    )

    config = tiny_config(n_experts=4, moe_top_k=2, mlp_dim=32)

    def actor_apply(params, tokens):
        return llama_apply(config, params, tokens)

    def critic_init(rng):
        return {"w": jax.random.normal(rng, (config.dim, 1)) * 0.02,
                "emb": jax.random.normal(
                    rng, (config.vocab_size, config.dim)) * 0.02}

    def critic_apply(params, tokens):
        h = params["emb"][tokens]
        return (h @ params["w"])[..., 0]

    engine = ModelEngine({
        "actor": ModelSpec(
            init_fn=lambda rng: llama_init(config, rng),
            apply_fn=actor_apply, trainable=True,
            optimizer=optax.adam(1e-4),
        ),
        "critic": ModelSpec(
            init_fn=critic_init, apply_fn=critic_apply,
            trainable=True, optimizer=optax.adam(1e-3),
        ),
    })

    def score_fn(sequences, gen_mask):
        gen = np.asarray(sequences)[:, -gen_mask.shape[1]:]
        return (np.asarray(gen) % 2 == 0).mean(axis=1)

    trainer = LMPPOTrainer(
        engine, PPOConfig(ppo_epochs=1, train_batch_size=4),
        llama_config=config, score_fn=score_fn,
        gen=GenerateConfig(max_new_tokens=4, temperature=1.0),
    )
    prompts = {"tokens": np.asarray(
        jax.random.randint(jax.random.key(5), (4, 5), 0, 64)
    )}
    stats = trainer.train([prompts], iterations=1)
    assert stats and np.isfinite(float(stats["policy_loss"]))
