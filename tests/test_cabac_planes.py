"""Serial CABAC plane loops and the coder each served crossing takes.

``encode_plane`` / ``decode_plane`` code a whole TU plane in one loop on
local state; they must give the bytes, the bits and the final coder state
of the per-bit ``encode`` / ``decode`` they stand for.  The second half
pins which coder ``auto`` picks for the two benchmark cells' boundary
crossings (a decode step: 4 x 1 x 4096; a prefill: 4 x {128, 256, 512} x
4096), so a change of path cannot pass as a change of speed.
"""

import numpy as np
import pytest

from repro.core import CodecConfig, cabac, calibrate
from repro.core.binarization import index_to_context_bits
from repro.launch.serve import build_parser

# encoder start state (low, rng) at which coding a one leaves rng < _BOT
# with low and low + rng in different top bytes: the carry-less underflow
# branch of the renormalisation
_UNDERFLOW_LOW, _UNDERFLOW_RNG = 0x00FFFFF0, 0x00010010


def _random(p1, n=3000, seed=0):
    bits = (np.random.default_rng(seed).random(n) < p1).astype(np.uint8)
    return lambda: (1, [(bits, 0)], None, None)


def _tu_planes(n_levels, seed=1):
    def case():
        rng = np.random.default_rng(seed)
        p = 0.6 ** np.arange(n_levels)
        idx = rng.choice(n_levels, size=4000, p=p / p.sum())
        planes = index_to_context_bits(idx, n_levels)
        return max(n_levels - 1, 1), list(zip(planes, range(len(planes)))), \
            None, None
    return case


CASES = {
    "empty": lambda: (1, [(np.zeros(0, np.uint8), 0)], None, None),
    "all_zero": lambda: (1, [(np.zeros(2000, np.uint8), 0)], None,
                         cabac._P_MIN),
    "all_one": lambda: (1, [(np.ones(2000, np.uint8), 0)], None,
                        cabac._P_MAX),
    "alternating": lambda: (1, [(np.arange(2001, dtype=np.uint8) & 1, 0)],
                            None, None),
    "random_p0.02": _random(0.02),
    "random_p0.5": _random(0.5),
    "random_p0.98": _random(0.98),
    "underflow": lambda: (1, [(np.array([1, 0, 1, 1, 0, 0, 1], np.uint8), 0)],
                          (_UNDERFLOW_LOW, _UNDERFLOW_RNG), None),
    "planes_n2": _tu_planes(2),
    "planes_n4": _tu_planes(4),
    "planes_n16": _tu_planes(16),
}


def _enc_state(e):
    return e.low, e.rng, bytes(e.out), [c.p1 for c in e.ctx]


def _dec_state(d):
    return d.low, d.rng, d.code, d.pos, [c.p1 for c in d.ctx]


@pytest.mark.parametrize("case", list(CASES))
def test_plane_loop_matches_per_bit_coder(case):
    n_ctx, planes, start, final_p1 = CASES[case]()
    fast = cabac.BinaryArithmeticEncoder(n_ctx)
    ref = cabac.BinaryArithmeticEncoder(n_ctx)
    if start is not None:
        low, rng = start
        r1 = (rng >> cabac._PROB_BITS) * ref.ctx[0].p1
        assert planes[0][0][0] == 1 and r1 < cabac._BOT
        assert (low ^ (low + r1)) & cabac._MASK >= cabac._TOP
        for e in (fast, ref):
            e.low, e.rng = start
    for bits, j in planes:
        fast.encode_plane(bits, j)
        for b in bits.tolist():
            ref.encode(b, j)
        assert _enc_state(fast) == _enc_state(ref)
    if final_p1 is not None:
        assert fast.ctx[0].p1 == final_p1
    blob = fast.finish()
    assert blob == ref.finish()

    fast_d = cabac.BinaryArithmeticDecoder(blob, n_ctx)
    ref_d = cabac.BinaryArithmeticDecoder(blob, n_ctx)
    if start is not None:
        for d in (fast_d, ref_d):
            d.low, d.rng = start
    for bits, j in planes:
        got = fast_d.decode_plane(bits.size, j)
        want = [ref_d.decode(j) for _ in range(bits.size)]
        assert got.dtype == np.uint8 and got.tolist() == want
        np.testing.assert_array_equal(got, bits)
        assert _dec_state(fast_d) == _dec_state(ref_d)


def _tensor_codec():
    """The cells' codec: ``serve.py``'s warm-up config at 4 levels, per
    tensor, clip mode ``model`` (``bench/configs/codeqwen1.5-7b.json``)."""
    samples = np.random.default_rng(2).standard_normal(1 << 14)
    return calibrate(CodecConfig(n_levels=4, clip_mode="model",
                                 constrain_cmin_zero=False,
                                 granularity="tensor", channel_axis=-1),
                     samples.astype(np.float32))


# encode_stream's own default chunk and the one serve.py hands the client
_CHUNKS = {"encode_stream": 1 << 18,
           "serve": build_parser().get_default("chunk_elems")}


@pytest.mark.parametrize("chunk", list(_CHUNKS))
def test_decode_step_crossing_is_one_serial_chunk(chunk):
    codec = _tensor_codec()
    x = np.random.default_rng(3).standard_normal((4, 1, 4096))
    payloads = list(codec.encode_stream(x.astype(np.float32),
                                        chunk_elems=_CHUNKS[chunk]))
    assert len(payloads) == 2              # stream header + one chunk
    assert payloads[1][:4] == b"\0\0\0\0"  # chunk id 0
    assert payloads[1][4] == cabac._CODER_SERIAL
    np.testing.assert_array_equal(codec.decode_stream(payloads),
                                  codec.decode(codec.encode(x),
                                               shape=x.shape))


@pytest.mark.parametrize("chunk", list(_CHUNKS))
@pytest.mark.parametrize("prompt", [128, 256, 512])
def test_prefill_crossing_chunks_are_rans(chunk, prompt):
    size = _CHUNKS[chunk]
    n = 4 * prompt * 4096
    assert n % size == 0
    idx = np.zeros(n, np.int32)      # all-zero: the fewest TU bits there are
    blobs = cabac.encode_indices_batch(
        [idx[c:c + size] for c in range(0, n, size)], 4)
    assert len(blobs) == n // size
    assert {b[0] for b in blobs} == {cabac._CODER_RANS}
