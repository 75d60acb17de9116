"""The check that decides ``correct`` fails the control and every fault
a cell can have, and passes the program as it is.

Smoke width on the CPU, with a limit of the test's own between the
program's and the control's readings there; the cells' limits come
from chip runs at their own sizes (PERF.md).  Faults are planted in the
timed path underneath the harness, which runs unchanged.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from benchkit import cell
from conftest import smoke_spec

SMOKE = {
    "codeqwen7b.prefill": dict(prompt_buckets=[16, 32, 64]),
    "codeqwen7b.decode": dict(prompt_buckets=[32, 64],
                              output_buckets=[4, 8, 16, 32]),
}
# smoke width: a test's own limits between the program's and the
# control's readings there (seeds 21-23: program <= 0.023 and <= 0.019,
# control >= 0.25 and >= 0.39)
LIMITS = {"boundary_err": 0.08, "logit_gap": 0.2}


def run(bench, name, seed=21, control=False):
    spec = smoke_spec(name, bench, **SMOKE[name])
    spec["workload"]["limits"] = LIMITS
    return cell.run(name, seed, 3.0, False, platform="cpu", bench=bench,
                    spec=spec, control=control, log=lambda m: None)


@pytest.mark.parametrize("name", sorted(SMOKE))
def test_program_passes_and_control_fails(bench, name):
    res = run(bench, name, control=True)
    c = res["checks"]
    assert res["correct"] is True
    for k, limit in LIMITS.items():
        assert c[k]["value"] < limit
    # the control fails the check: one of its numbers is past its limit
    # and reads three times the program's or more
    assert any(c[f"control_{k}"]["value"] > max(limit, 3 * c[k]["value"])
               for k, limit in LIMITS.items())


def _state_unchanged(monkeypatch):
    import repro.serving.engine as E
    pre, post = E.decode_to_boundary, E.decode_from_boundary

    def to_b(cfg, p, t, c, pos, ctx=None):
        x, new = pre(cfg, p, t, c, pos, ctx=ctx)
        return x, list(c[:len(new)])

    def from_b(cfg, p, x, c, pos, ctx=None):
        logits, new = post(cfg, p, x, c, pos, ctx=ctx)
        return logits, list(c[len(c) - len(new):])

    monkeypatch.setattr(E, "decode_to_boundary", to_b)
    monkeypatch.setattr(E, "decode_from_boundary", from_b)


def _half_batch(monkeypatch):
    import repro.launch.serve as S
    orig = S._loopback_codec_fn

    def patched(*a, **kw):
        roundtrip, cleanup = orig(*a, **kw)

        def half(x):
            recon, bpe = roundtrip(x)
            recon = np.array(recon)
            recon[x.shape[0] // 2:] = 0.0
            return recon, bpe
        return half, cleanup

    monkeypatch.setattr(S, "_loopback_codec_fn", patched)


def _token_altered(monkeypatch):
    import repro.serving.engine as E
    pre_f, dec_f = E.prefill_from_boundary, E.decode_from_boundary

    def prefill(cfg, p, x, c, ctx=None):
        logits, new = pre_f(cfg, p, x, c, ctx=ctx)
        return jnp.roll(logits, 1, axis=-1), new

    def decode(cfg, p, x, c, pos, ctx=None):
        logits, new = dec_f(cfg, p, x, c, pos, ctx=ctx)
        return jnp.roll(logits, 1, axis=-1), new

    monkeypatch.setattr(E, "prefill_from_boundary", prefill)
    monkeypatch.setattr(E, "decode_from_boundary", decode)


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "token_altered": _token_altered}
CASES = [("codeqwen7b.decode", f) for f in FAULTS] + [
    # one output token: no decode step whose state could stay unchanged
    ("codeqwen7b.prefill", "half_batch"),
    ("codeqwen7b.prefill", "token_altered")]


@pytest.mark.parametrize("name,fault", CASES)
def test_fault_fails_the_check(bench, monkeypatch, name, fault):
    FAULTS[fault](monkeypatch)
    res = run(bench, name)
    assert res["correct"] is False
    assert any(res["checks"][k]["value"] > limit
               for k, limit in LIMITS.items())


def test_gap_counts_past_one_bf16_step():
    from benchkit import correct
    # best 4.5: bfloat16's step there is 2**-5
    lg = np.zeros((1, 3, 4))
    lg[0, :, 0] = 4.5
    lg[0, :, 1] = [4.5 - 0.03, 4.5 - 0.05, 4.5]
    assert correct.bf16_step(np.array([4.5, 1.0, 0.75])).tolist() == \
        [2.0 ** -5, 2.0 ** -7, 2.0 ** -8]
    # tokens within one step of the best read 0; past it, the excess
    assert correct._widest_gap(lg[:, :1], [1]) == 0.0
    assert correct._widest_gap(lg, [1, 1, 1]) == pytest.approx(
        0.05 - 2.0 ** -5)
    assert correct._widest_gap(lg, [0, 0, 0]) == 0.0
