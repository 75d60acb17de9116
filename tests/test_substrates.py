"""Substrate tests: data pipeline, checkpoint/restore + failure injection,
trainer convergence, gradient compression, serving engine."""

import dataclasses
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compression import (GradCompressionConfig, compress_grads,
                               init_error_feedback)
from repro.configs import ARCHS, reduced
from repro.data import DataConfig, stream
from repro.models import init_params
from repro.train import Trainer, TrainerConfig, checkpoint as ckpt


@pytest.fixture()
def tiny_cfg():
    return dataclasses.replace(reduced(ARCHS["codeqwen1.5-7b"]),
                               vocab_size=128, d_model=32, d_ff=64,
                               num_heads=2, num_kv_heads=2, head_dim=16)


class TestData:
    def test_deterministic_replay(self):
        cfg = DataConfig(vocab_size=100, batch=4, seq_len=16)
        a = list(zip(range(5), stream(cfg)))
        b = list(zip(range(5), stream(cfg)))
        for (_, x), (_, y) in zip(a, b):
            np.testing.assert_array_equal(x["tokens"], y["tokens"])

    def test_resume_mid_stream(self):
        cfg = DataConfig(vocab_size=100, batch=2, seq_len=8)
        full = [b["tokens"] for _, b in zip(range(6), stream(cfg))]
        resumed = [b["tokens"] for _, b in zip(range(3), stream(cfg, 3))]
        for x, y in zip(full[3:], resumed):
            np.testing.assert_array_equal(x, y)

    def test_tokens_in_range(self):
        cfg = DataConfig(vocab_size=37, batch=2, seq_len=64)
        b = next(stream(cfg))
        assert b["tokens"].min() >= 0 and b["tokens"].max() < 37


class TestCheckpoint:
    def test_roundtrip(self, tmp_path, tiny_cfg):
        params = init_params(tiny_cfg, jax.random.PRNGKey(0))
        ckpt.save(str(tmp_path), 7, {"params": params})
        assert ckpt.latest_step(str(tmp_path)) == 7
        restored = ckpt.restore(str(tmp_path), 7, {"params": params})
        for a, b in zip(jax.tree.leaves(params),
                        jax.tree.leaves(restored["params"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_gc_keeps_latest(self, tmp_path, tiny_cfg):
        params = {"w": jnp.ones((4,))}
        for s in (1, 2, 3, 4, 5):
            ckpt.save(str(tmp_path), s, params, keep=2)
        steps = sorted(os.listdir(tmp_path))
        assert steps == ["step_00000004", "step_00000005"]

    def test_atomic_no_tmp_left(self, tmp_path):
        ckpt.save(str(tmp_path), 1, {"w": jnp.ones((4,))})
        assert not any(d.endswith(".tmp") for d in os.listdir(tmp_path))


class TestTrainer:
    def _mk(self, tiny_cfg, tmp_path, **kw):
        tcfg = TrainerConfig(steps=8, ckpt_every=4, ckpt_dir=str(tmp_path),
                             warmup_steps=2, **kw)
        dcfg = DataConfig(vocab_size=tiny_cfg.vocab_size, batch=2, seq_len=16)
        return Trainer(tiny_cfg, tcfg, dcfg)

    def test_loss_decreases(self, tiny_cfg, tmp_path):
        tr = self._mk(tiny_cfg, tmp_path)
        tr.run(resume=False)
        losses = [m["loss"] for m in tr.metrics_log]
        assert losses[-1] < losses[0]
        assert np.isfinite(losses).all()

    def test_failure_injection_and_bitexact_resume(self, tiny_cfg, tmp_path):
        full = self._mk(tiny_cfg, tmp_path)
        state_full = full.run(resume=False)
        shutil.rmtree(tmp_path)
        crash = self._mk(tiny_cfg, tmp_path)
        crash.fail_at_step = 5  # after the step-4 checkpoint
        with pytest.raises(RuntimeError, match="injected failure"):
            crash.run(resume=False)
        assert ckpt.latest_step(str(tmp_path)) == 4
        resumed = self._mk(tiny_cfg, tmp_path)
        state_res = resumed.run(resume=True)  # restarts from step 4
        for a, b in zip(jax.tree.leaves(state_full["params"]),
                        jax.tree.leaves(state_res["params"])):
            np.testing.assert_allclose(np.asarray(a, np.float64),
                                       np.asarray(b, np.float64),
                                       rtol=1e-6, atol=1e-6)

    def test_grad_compression_training_still_converges(self, tiny_cfg, tmp_path):
        tr = self._mk(tiny_cfg, tmp_path,
                      grad_compression=GradCompressionConfig(n_levels=16))
        tr.run(resume=False)
        losses = [m["loss"] for m in tr.metrics_log]
        assert losses[-1] < losses[0]


class TestGradCompression:
    def test_error_feedback_preserves_mean_update(self):
        """EF: sum of compressed grads ~= sum of raw grads over time."""
        cfg = GradCompressionConfig(n_levels=4)
        rng = np.random.default_rng(0)
        g_raw = [{"w": jnp.asarray(rng.normal(size=(64,)).astype(np.float32))}
                 for _ in range(30)]
        ef = init_error_feedback(g_raw[0])
        total_c = jnp.zeros((64,))
        for g in g_raw:
            cg, ef, _ = compress_grads(cfg, g, ef)
            total_c = total_c + cg["w"]
        total_raw = sum(g["w"] for g in g_raw)
        resid = np.abs(np.asarray(total_c - total_raw)).max()
        per_step_q = float(np.asarray(ef["w"]).std()) + 1e-9
        # residual stays bounded by one step's quantization error, not O(T)
        assert resid < 10 * per_step_q

    def test_disabled_passthrough(self):
        cfg = GradCompressionConfig(enabled=False)
        g = {"w": jnp.arange(8.0)}
        ef = init_error_feedback(g)
        cg, _, _ = compress_grads(cfg, g, ef)
        np.testing.assert_array_equal(np.asarray(cg["w"]), np.asarray(g["w"]))

    def test_bf16_residual_accounts_for_cast(self):
        """EF invariant under low-precision grads: cg + new_e == gf.

        The compressed grad is cast to g.dtype before the reduction, so
        under bf16 the residual must be measured against the *cast*
        value -- otherwise the per-step cast rounding (up to ~2^-8
        relative) silently leaks out of the feedback loop.
        """
        cfg = GradCompressionConfig(n_levels=4)
        rng = np.random.default_rng(7)
        g = {"w": jnp.asarray(rng.normal(size=(256,)), jnp.bfloat16)}
        ef = init_error_feedback(g)
        # a couple of steps so the residual buffer is non-trivial
        for _ in range(3):
            gf = np.asarray(g["w"], np.float32) + np.asarray(ef["w"])
            cg, ef, _ = compress_grads(cfg, g, ef)
            assert cg["w"].dtype == jnp.bfloat16
            recon = (np.asarray(cg["w"], np.float32)
                     + np.asarray(ef["w"], np.float32))
            np.testing.assert_allclose(recon, gf, rtol=0, atol=1e-6)


class TestServing:
    def test_engine_generates(self, tiny_cfg):
        from repro.serving import Request, ServeEngine
        params = init_params(tiny_cfg, jax.random.PRNGKey(0))
        eng = ServeEngine(tiny_cfg, params, slots=2, max_seq=64)
        reqs = [Request(prompt=np.arange(5, dtype=np.int32) + i,
                        max_new_tokens=4) for i in range(3)]
        out = eng.generate(reqs)
        assert all(r.done and len(r.out_tokens) == 4 for r in out)
        assert all(0 <= t < tiny_cfg.vocab_size
                   for r in out for t in r.out_tokens)

    def test_engine_with_codec_logs_rate(self, tiny_cfg):
        from repro.core import CodecConfig, calibrate
        from repro.serving import Request, ServeEngine
        codec = calibrate(CodecConfig(n_levels=4, clip_mode="manual",
                                      manual_cmin=-6.0, manual_cmax=6.0))

        def codec_fn(x):
            return codec.apply(x), codec.estimate_rate(x)

        params = init_params(tiny_cfg, jax.random.PRNGKey(0))
        eng = ServeEngine(tiny_cfg, params, slots=2, max_seq=64,
                          codec_fn=codec_fn)
        reqs = [Request(prompt=np.arange(4, dtype=np.int32), max_new_tokens=3)]
        eng.generate(reqs)
        assert len(eng.rate_log) > 0
        # entropy-coded TU bits/elem for N=4 is bounded by the max TU length
        assert all(0 <= r <= 3.0 for r in eng.rate_log)

    def test_slot_refill_staggered_lengths(self, tiny_cfg):
        """Short requests free their slot mid-epoch and queued requests
        are admitted without waiting for the longest request."""
        from repro.serving import Request, ServeEngine
        params = init_params(tiny_cfg, jax.random.PRNGKey(0))
        eng = ServeEngine(tiny_cfg, params, slots=2, max_seq=64)
        rng = np.random.default_rng(0)
        reqs = [Request(prompt=rng.integers(0, 128, 5).astype(np.int32),
                        max_new_tokens=m) for m in (2, 9, 3, 4, 1)]
        eng.generate(reqs)
        for r in reqs:
            assert r.done and len(r.out_tokens) == r.max_new_tokens
            assert r.latency_s is not None and r.latency_s >= 0
            assert r.ttft_s is not None and 0 <= r.ttft_s <= r.latency_s
        assert eng.metrics.get("repro_engine_ttft_seconds").count() \
            == len(reqs)

    def test_refilled_request_keeps_first_token(self, tiny_cfg):
        """Regression: the refill path must record the prefill argmax as
        the request's first generated token, not silently consume it."""
        import jax.numpy as jnp

        from repro.models import init_cache, prefill
        from repro.serving import Request, ServeEngine
        params = init_params(tiny_cfg, jax.random.PRNGKey(0))
        eng = ServeEngine(tiny_cfg, params, slots=1, max_seq=64)
        rng = np.random.default_rng(3)
        a = Request(prompt=rng.integers(0, 128, 5).astype(np.int32),
                    max_new_tokens=2)
        b = Request(prompt=rng.integers(0, 128, 5).astype(np.int32),
                    max_new_tokens=3)
        eng.generate([a, b])
        # b was refilled at pos 6 (a's 5-token prompt + 1 decode step);
        # reproduce its batch-1 left-padded prefill independently
        toks = np.zeros((1, 6), np.int32)
        toks[0, 1:] = b.prompt
        cache = init_cache(tiny_cfg, batch=1, max_seq=64)
        logits, _ = prefill(tiny_cfg, params, jnp.asarray(toks), cache)
        assert b.out_tokens[0] == int(jnp.argmax(logits[0]))
        assert len(b.out_tokens) == 3

    def test_slot_refill_zero_token_requests(self, tiny_cfg):
        from repro.serving import Request, ServeEngine
        params = init_params(tiny_cfg, jax.random.PRNGKey(0))
        eng = ServeEngine(tiny_cfg, params, slots=2, max_seq=64)
        reqs = [Request(prompt=np.arange(4, dtype=np.int32),
                        max_new_tokens=0),
                Request(prompt=np.arange(4, dtype=np.int32),
                        max_new_tokens=2)]
        eng.generate(reqs)
        assert reqs[0].done and reqs[0].out_tokens == []
        assert reqs[1].done and len(reqs[1].out_tokens) == 2
