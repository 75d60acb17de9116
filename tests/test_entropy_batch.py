"""Batched entropy coding tests.

``decode_indices_batch`` must be result-identical to per-payload
``decode_indices`` for any mix of coders, and ``encode_indices_batch``
must pick each segment's coder by the same ``auto`` rule as
``encode_indices``.
"""

import pathlib
import sys

import numpy as np
import pytest

from repro.core import cabac, rans
from repro.core import CodecConfig, calibrate
from repro.core.binarization import index_to_context_bits, total_tu_bits

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from golden_cases import build_sharded_payload  # noqa: E402


class TestBatchPlaneDecoder:
    @pytest.mark.parametrize("seed", range(4))
    def test_matches_serial_decoder(self, seed):
        rng = np.random.default_rng(seed)
        streams = []
        for _ in range(int(rng.integers(2, 6))):
            # totals pinned inside one lane bucket so the streams are
            # batchable (callers group by the blob's lane count), while
            # plane counts/sizes and probabilities still vary per stream
            n_planes = int(rng.integers(1, 4))
            sizes = rng.multinomial(
                int(rng.integers(34000, 40000)),
                np.ones(n_planes) / n_planes) + 1
            planes = [(rng.random(int(s)) < rng.random()).astype(np.uint8)
                      for s in sizes]
            streams.append(planes)
        blobs = [rans.encode_planes(p) for p in streams]
        lanes = {rans.PlaneStreamDecoder(b).lanes for b in blobs}
        assert len(lanes) == 1, "test construction: one lane bucket"
        batch = rans.BatchPlaneDecoder(blobs)
        serial = [rans.PlaneStreamDecoder(b) for b in blobs]
        n_planes = max(len(p) for p in streams)
        for j in range(n_planes):
            sizes = [s[j].size if j < len(s) else 0 for s in streams]
            got = batch.next_planes(sizes)
            for s, (size, out) in enumerate(zip(sizes, got)):
                want = serial[s].next_plane(size)
                np.testing.assert_array_equal(out, want)
                if size:
                    np.testing.assert_array_equal(out, streams[s][j])

    def test_rejects_mixed_lanes_and_empty(self):
        a = rans.encode_planes([np.ones(40000, np.uint8)])
        b = rans.encode_planes([np.zeros(10, np.uint8)])
        with pytest.raises(ValueError):
            rans.BatchPlaneDecoder([a, b])
        import struct
        empty = struct.pack("<HI", 0, 0)
        with pytest.raises(ValueError):
            rans.BatchPlaneDecoder([a, empty])


class TestDecodeIndicesBatch:
    @pytest.mark.parametrize("n_levels", [2, 3, 4, 8, 17])
    def test_identical_to_per_payload(self, n_levels):
        rng = np.random.default_rng(n_levels)
        segs = [rng.choice(n_levels, size=int(s)).astype(np.int32)
                for s in (1, 500, 65536, 66000, 150000, 150001)]
        blobs = [cabac.encode_indices(s, n_levels) for s in segs]
        batch = cabac.decode_indices_batch(blobs,
                                           [s.size for s in segs], n_levels)
        for s, blob, out in zip(segs, blobs, batch):
            np.testing.assert_array_equal(
                out, cabac.decode_indices(blob, s.size, n_levels))
            np.testing.assert_array_equal(out, s)

    def test_mixed_coders_and_degenerate(self):
        rng = np.random.default_rng(0)
        segs = [np.zeros(5, np.int32),
                np.full(130000, 7, np.int32),
                rng.choice(8, size=200000).astype(np.int32),
                rng.choice(2, size=70000).astype(np.int32)]
        blobs = [cabac.encode_indices(s, 8) for s in segs]
        blobs.append(build_sharded_payload(segs[2], 8, n_shards=2))
        segs.append(segs[2])
        out = cabac.decode_indices_batch(blobs, [s.size for s in segs], 8)
        for s, o in zip(segs, out):
            np.testing.assert_array_equal(o, s)

    def test_single_member_group(self):
        rng = np.random.default_rng(1)
        seg = rng.choice(4, size=90000).astype(np.int32)
        blob = cabac.encode_indices(seg, 4, mode="rans")
        (out,) = cabac.decode_indices_batch([blob], [seg.size], 4)
        np.testing.assert_array_equal(out, seg)


class TestStreamBatchedDecode:
    def test_chunk_batching_bit_exact_any_order(self):
        from repro.core import ChunkStreamDecoder
        rng = np.random.default_rng(3)
        x = rng.exponential(1.0, (64, 1024)).astype(np.float32)
        codec = calibrate(CodecConfig(n_levels=4, clip_mode="minmax",
                                      constrain_cmin_zero=False), samples=x)
        payloads = list(codec.encode_stream(x, chunk_elems=3000))
        one_shot = codec.decode(codec.encode(x), shape=x.shape)
        for batch in (1, 3, len(payloads)):
            dec = ChunkStreamDecoder(payloads[0], chunk_batch=batch)
            order = rng.permutation(len(payloads) - 1)
            for k in order:
                dec.add_chunk(payloads[1 + k])
            np.testing.assert_array_equal(dec.finish(), one_shot)

    def test_corrupt_chunk_does_not_poison_stream(self):
        from repro.core import ChunkStreamDecoder
        rng = np.random.default_rng(6)
        x = rng.exponential(1.0, (8192,)).astype(np.float32)
        codec = calibrate(CodecConfig(n_levels=4, clip_mode="minmax",
                                      constrain_cmin_zero=False), samples=x)
        payloads = list(codec.encode_stream(x, chunk_elems=1000))
        dec = ChunkStreamDecoder(payloads[0], chunk_batch=1)
        bad = payloads[1][:4] + bytes([255]) + payloads[1][5:]  # coder id
        with pytest.raises(ValueError):
            dec.add_chunk(bad)
        # the failed chunk is re-requestable -- not a duplicate
        for p in payloads[1:]:
            dec.add_chunk(p)
        np.testing.assert_array_equal(
            dec.finish(), codec.decode(codec.encode(x), shape=x.shape))

    def test_truncated_member_raises_in_batch(self):
        rng = np.random.default_rng(7)
        segs = [rng.choice(4, size=90000).astype(np.int32) for _ in range(3)]
        blobs = [cabac.encode_indices(s, 4, mode="rans") for s in segs]
        cut = blobs[1][:len(blobs[1]) - 40]     # drop trailing words
        with pytest.raises(ValueError):
            cabac.decode_indices_batch([blobs[0], cut, blobs[2]],
                                       [s.size for s in segs], 4)

    def test_duplicate_rejected_before_batch_flush(self):
        from repro.core import ChunkStreamDecoder
        rng = np.random.default_rng(4)
        x = rng.exponential(1.0, (4096,)).astype(np.float32)
        codec = calibrate(CodecConfig(n_levels=4, clip_mode="minmax",
                                      constrain_cmin_zero=False), samples=x)
        payloads = list(codec.encode_stream(x, chunk_elems=500))
        dec = ChunkStreamDecoder(payloads[0], chunk_batch=64)
        dec.add_chunk(payloads[1])
        with pytest.raises(ValueError, match="duplicate"):
            dec.add_chunk(payloads[1])
        with pytest.raises(ValueError, match="incomplete"):
            dec.finish()


class TestAutoRule:
    """``encode_indices`` and ``encode_indices_batch`` share one ``auto``
    rule: serial CABAC (id 0) below ``_SERIAL_CUTOFF_BITS`` TU bits,
    rANS (id 1) at or above it."""

    @pytest.mark.parametrize("n,n_levels,coder_id", [
        (0, 4, 0),
        (5_000, 4, 0),
        (65_535, 2, 0),         # one TU bit an element: just under
        (40_000, 8, 1),         # TU bits above the cutoff, fewer elements
        (1 << 16, 2, 1),        # the element count alone settles it
    ])
    def test_same_coder_either_side_of_cutoff(self, n, n_levels, coder_id):
        rng = np.random.default_rng(n)
        seg = rng.integers(0, n_levels, n).astype(np.int32)
        bits = total_tu_bits(seg, n_levels)
        assert (bits >= cabac._SERIAL_CUTOFF_BITS) == bool(coder_id)
        one = cabac.encode_indices(seg, n_levels)
        (batch,) = cabac.encode_indices_batch([seg], n_levels)
        assert one[0] == batch[0] == coder_id
        assert one == batch


class TestEncoderCompaction:
    """The compacted TU plane builder must match the straightforward
    definition (plane j = bits of elements with idx >= j)."""

    @pytest.mark.parametrize("n_levels", [2, 4, 9])
    def test_planes_match_definition(self, n_levels):
        rng = np.random.default_rng(n_levels)
        idx = rng.choice(n_levels, size=5000).astype(np.int32)
        planes = index_to_context_bits(idx, n_levels)
        assert len(planes) == n_levels - 1
        for j, plane in enumerate(planes):
            alive = idx >= j
            np.testing.assert_array_equal(plane,
                                          (idx[alive] > j).astype(np.uint8))
