"""System-level property tests (hypothesis) for codec invariants."""

import struct

import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import example, given, settings, strategies as st

from repro.core import binarization, cabac, uniform
from repro.core.rate_model import estimated_bits_np


@settings(max_examples=30, deadline=None)
@given(data=st.lists(st.integers(0, 7), min_size=0, max_size=800),
       n_levels=st.integers(2, 8))
def test_cabac_roundtrip_any_sequence(data, n_levels):
    idx = np.asarray([d % n_levels for d in data], dtype=np.int32)
    blob = cabac.encode_indices(idx, n_levels)
    back = cabac.decode_indices(blob, idx.size, n_levels)
    assert (back == idx).all()


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 2**16), n_levels=st.integers(2, 16),
       cmax=st.floats(0.5, 30.0))
def test_quantizer_error_bounded_by_half_bin(seed, n_levels, cmax):
    """Inside the clip range, |x - deq(q(x))| <= delta/2 (pinned bins)."""
    rng = np.random.default_rng(seed)
    x = rng.uniform(0.0, cmax, size=500)
    q = uniform.quantize_np(x, 0.0, cmax, n_levels)
    deq = uniform.dequantize_np(q, 0.0, cmax, n_levels)
    delta = cmax / (n_levels - 1)
    assert np.max(np.abs(x - deq)) <= delta / 2 + 1e-9


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**16))
def test_rate_monotone_in_levels(seed):
    """More quantizer levels never decreases the entropy-coded rate."""
    rng = np.random.default_rng(seed)
    x = rng.gamma(2.0, 2.0, size=20_000)
    rates = []
    for n in (2, 3, 4, 6, 8):
        idx = uniform.quantize_np(x, 0.0, 10.0, n)
        rates.append(estimated_bits_np(idx, n) / idx.size)
    assert all(a <= b + 1e-6 for a, b in zip(rates, rates[1:]))


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**16), n_levels=st.integers(2, 8))
def test_tu_bits_upper_bound_entropy_estimate(seed, n_levels):
    """Entropy-coded estimate never exceeds raw TU bits."""
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, n_levels, size=5000).astype(np.int32)
    est = estimated_bits_np(idx, n_levels)
    raw = binarization.total_tu_bits(idx, n_levels)
    assert est <= raw + 1e-6


def _empirical_entropy_bits(idx: np.ndarray, n_levels: int) -> float:
    """Zeroth-order empirical entropy of the sample times its size."""
    counts = np.bincount(idx, minlength=n_levels)
    p = counts[counts > 0] / idx.size
    return float(-(p * np.log2(p)).sum()) * idx.size


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**16), n_levels=st.integers(2, 8),
       skew=st.floats(0.05, 0.95))
@example(seed=1, n_levels=16, skew=0.05)    # near-uniform, takes rANS
@example(seed=1, n_levels=16, skew=0.10)    # near-uniform, takes serial
@example(seed=4, n_levels=8, skew=0.10)
def test_cabac_beats_or_matches_fixed_width(seed, n_levels, skew):
    """Coded size stays within what the chosen coder promises over the
    sample's zeroth-order empirical entropy.

    TU bins with one probability per bin position code a sample at
    exactly ``idx.size * H0`` bits under ideal probabilities (the chain
    rule over the bins), so each coder's documented overhead bounds the
    rest: serial CABAC (id 0) its id byte, 4-byte flush and adaptive
    estimate (under 0.02 bits a TU bin plus 32 bits of learning a
    context, ``cabac`` docstring); rANS (id 1) its id byte, ``<HI>``
    header, 4-byte state flush a lane and 2-byte probability a span
    (``rans`` docstring).
    """
    rng = np.random.default_rng(seed)
    p = np.full(n_levels, (1 - skew) / max(n_levels - 1, 1))
    p[0] = skew
    idx = rng.choice(n_levels, size=8000, p=p).astype(np.int32)
    blob = cabac.encode_indices(idx, n_levels)
    bound = _empirical_entropy_bits(idx, n_levels)
    if blob[0] == cabac._CODER_SERIAL:
        tu_bits = binarization.total_tu_bits(idx, n_levels)
        bound += 8 + 32 + 0.02 * tu_bits + 32 * (n_levels - 1)
    else:
        assert blob[0] == cabac._CODER_RANS
        lanes, n_spans = struct.unpack_from("<HI", blob, 1)
        bound += 8 + 48 + 32 * lanes + 16 * n_spans
    assert len(blob) * 8 <= bound
