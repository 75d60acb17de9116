"""Fused encode megakernel parity suite.

The single-pass encode contract: one device pass (clip -> quantize ->
bit-pack -> per-tile histogram) whose packed bytes + histograms are the
only device->host transfer, with coded-order indices bit-identical to the
unfused quantize path on *every* backend -- which is what keeps the
entropy payload byte-identical.  Kernels run in interpret mode on CPU;
the jnp backend fulfils the same contract with its reference formulas.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import CodecConfig, calibrate
from repro.core.backend import QuantSpec, _coded_order, get_backend
from repro.core.tiling import TileECSQ, TilePlan
from repro.kernels import ops


def _bits(n_levels: int) -> int:
    return max(1, int(np.ceil(np.log2(n_levels))))


@pytest.fixture(scope="module")
def backends():
    return get_backend("jnp"), get_backend("kernel_interpret")


class TestFusedPerTensor:
    @pytest.mark.parametrize("n", [1, 513, 1000, 4096, 1 << 16])
    @pytest.mark.parametrize("n_levels", [2, 3, 4, 5, 8, 17, 64])
    def test_fused_equals_unfused(self, backends, n, n_levels):
        rng = np.random.default_rng(n + n_levels)
        x = jnp.asarray(rng.normal(2, 3, (n,)).astype(np.float32))
        spec = QuantSpec(0.0, 7.5, n_levels)
        for be in backends:
            coded, hists = be.encode_fused(x, spec, _bits(n_levels),
                                           want_hist=True)
            unfused = _coded_order(np.asarray(be.quantize(x, spec)), spec)
            np.testing.assert_array_equal(coded, unfused)
            assert hists.shape == (1, 1, n_levels)
            np.testing.assert_array_equal(
                hists.ravel(), np.bincount(unfused, minlength=n_levels))

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes_fused_matches_own_backend(self, backends, dtype):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(1, 2, (3, 7, 41)), dtype)
        spec = QuantSpec(-1.0, 5.0, 8)
        for be in backends:
            coded, _ = be.encode_fused(x, spec, 3)
            np.testing.assert_array_equal(
                coded, np.asarray(be.quantize(x, spec)).ravel())

    def test_backends_agree_f32(self, backends):
        jb, kb = backends
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(0, 4, (2000,)).astype(np.float32))
        spec = QuantSpec(-3.0, 3.0, 4)
        cj, hj = jb.encode_fused(x, spec, 2, want_hist=True)
        ck, hk = kb.encode_fused(x, spec, 2, want_hist=True)
        np.testing.assert_array_equal(cj, ck)
        np.testing.assert_array_equal(hj, hk)


class TestMultiRowBlock:
    """Views taller than one 256-row kernel block: the bit-pack works
    per row block, so the unpack must find every block's slabs."""

    @pytest.mark.parametrize("granularity", ["tensor", "channel"])
    @pytest.mark.parametrize("n_levels", [2, 4, 16])
    def test_kernel_matches_jnp(self, backends, granularity, n_levels):
        rng = np.random.default_rng(n_levels)
        if granularity == "tensor":
            x = rng.normal(0, 2, (300_000,)).astype(np.float32)
            spec = QuantSpec(-3.0, 3.0, n_levels)
        else:
            x = rng.normal(0, 2, (4, 8, 300)).astype(np.float32)
            lo = np.full((300, 1), -3.0, np.float32) \
                + rng.uniform(0, 1, (300, 1)).astype(np.float32)
            plan = TilePlan(channel_axis=-1, channel_group_size=1,
                            spatial_block_size=0, n_channels=300)
            spec = QuantSpec(lo, lo + 5.0, n_levels, -1, None, plan)
        jb, kb = backends
        xj = jnp.asarray(x)
        cj, hj = jb.encode_fused(xj, spec, _bits(n_levels), want_hist=True)
        ck, hk = kb.encode_fused(xj, spec, _bits(n_levels), want_hist=True)
        np.testing.assert_array_equal(cj, ck)
        np.testing.assert_array_equal(hj, hk)
        np.testing.assert_array_equal(
            np.asarray(kb.coded_indices_device(xj, spec, _bits(n_levels))),
            cj)


class TestFusedTiled:
    @pytest.mark.parametrize("geom", [
        # (shape, group, spatial_block): non-multiple channel counts,
        # non-multiple spatial blocks, one-spatial-block (per-channel)
        ((7, 11, 17), 4, 30),
        ((5, 64), 1, 0),
        ((16, 16, 3), 2, 7),
        ((33, 129), 5, 100),
    ])
    @pytest.mark.parametrize("n_levels", [2, 4, 6, 16, 33])
    def test_fused_equals_unfused(self, backends, geom, n_levels):
        shape, gs, bs = geom
        rng = np.random.default_rng(n_levels)
        x = rng.normal(1, 2, shape).astype(np.float32)
        c = shape[-1]
        m = int(np.prod(shape)) // c
        plan = TilePlan(channel_axis=-1, channel_group_size=gs,
                        spatial_block_size=bs, n_channels=c,
                        spatial_extent=m if bs else None)
        lo = rng.normal(-3, 0.1,
                        (plan.n_cgroups, plan.n_sblocks)).astype(np.float32)
        hi = lo + rng.uniform(1, 5, lo.shape).astype(np.float32)
        spec = QuantSpec(lo, hi, n_levels, -1, None, plan)
        xj = jnp.asarray(x)
        results = []
        for be in backends:
            coded, hists = be.encode_fused(xj, spec, _bits(n_levels),
                                           want_hist=True)
            unfused = plan.to_coded_order(np.asarray(be.quantize(xj, spec)))
            np.testing.assert_array_equal(coded, unfused)
            assert int(hists.sum()) == x.size
            # per-tile counts match host bincounts over the tile map
            tid = plan.tile_ids(x.shape)
            flat = hists.reshape(plan.n_tiles, n_levels)
            idx_full = plan.from_coded_order(coded, x.shape)
            for t in range(plan.n_tiles):
                np.testing.assert_array_equal(
                    flat[t], np.bincount(idx_full[tid == t],
                                         minlength=n_levels))
            results.append((coded, hists))
        np.testing.assert_array_equal(results[0][0], results[1][0])
        np.testing.assert_array_equal(results[0][1], results[1][1])

    def test_tile_histogram_matches_fused(self, backends):
        rng = np.random.default_rng(5)
        x = rng.normal(1, 2, (7, 11, 17)).astype(np.float32)
        plan = TilePlan(channel_axis=-1, channel_group_size=4,
                        spatial_block_size=30, n_channels=17,
                        spatial_extent=77)
        lo = np.full((plan.n_cgroups, plan.n_sblocks), -2.0, np.float32)
        hi = np.full_like(lo, 4.0)
        spec = QuantSpec(lo, hi, 6, -1, None, plan)
        for be in backends:
            idx = be.quantize(jnp.asarray(x), spec)
            th = np.asarray(be.tile_histogram(idx, spec))
            _, fused = be.encode_fused(jnp.asarray(x), spec, 3,
                                       want_hist=True)
            np.testing.assert_array_equal(th, fused)


class TestFusedCodecStreams:
    @pytest.mark.parametrize("granularity,kw", [
        ("tensor", {}),
        ("channel", {}),
        ("tile", {"spatial_block_size": 1000}),
    ])
    @pytest.mark.parametrize("coder_mode", ["serial", "rans"])
    def test_encode_byte_identical(self, granularity, kw, coder_mode):
        rng = np.random.default_rng(7)
        mu = np.linspace(0.0, 6.0, 16).astype(np.float32)
        x = (mu[None] + rng.exponential(1.0, (256, 16))).astype(np.float32)
        cfg = CodecConfig(n_levels=4, clip_mode="minmax",
                          constrain_cmin_zero=False,
                          granularity=granularity, channel_axis=-1,
                          channel_group_size=3, **kw)
        codec = calibrate(cfg, samples=x)
        fused = codec.encode(x, coder_mode=coder_mode)
        unfused = codec.encode(x, coder_mode=coder_mode, fused=False)
        assert fused == unfused
        np.testing.assert_array_equal(codec.decode(fused, shape=x.shape),
                                      codec.decode(unfused, shape=x.shape))

    def test_ecsq_falls_back_bit_exact(self):
        rng = np.random.default_rng(11)
        x = rng.exponential(1.0, (4096,)).astype(np.float32)
        codec = calibrate(CodecConfig(n_levels=4, use_ecsq=True,
                                      clip_mode="minmax",
                                      constrain_cmin_zero=False),
                          samples=x)
        assert codec.encode(x) == codec.encode(x, fused=False)


class TestUnpackBytes:
    @pytest.mark.parametrize("rows", [16, 512])
    @pytest.mark.parametrize("bits", [1, 2, 4, 3, 6, 8])
    def test_pack_unpack_roundtrip_layout(self, bits, rows):
        """Megakernel byte layout: each row block of min(256, R) rows
        splits into ``per`` row slabs, slab k at bit offset k * bits."""
        rng = np.random.default_rng(bits)
        per = 8 // bits if bits in (1, 2, 4) else 1
        vals = rng.integers(0, 1 << min(bits, 8),
                            size=(rows, 128)).astype(np.int32)
        if per == 1:
            packed = vals.astype(np.uint8)
        else:
            br = min(256, rows)
            slabs = vals.reshape(rows // br, per, br // per, 128)
            shifts = (np.arange(per, dtype=np.uint8) * bits)[:, None, None]
            packed = np.sum(slabs.astype(np.uint8) << shifts, axis=1,
                            dtype=np.uint8).reshape(rows // per, 128)
        np.testing.assert_array_equal(ops.unpack_bytes(packed, bits), vals)


class TestTiledECSQKernel:
    @pytest.mark.parametrize("n_levels", [4, 17, 33, 64])
    def test_parity_with_jnp(self, backends, n_levels):
        jb, kb = backends
        rng = np.random.default_rng(n_levels)
        x = rng.normal(1, 2, (7, 11, 17)).astype(np.float32)
        plan = TilePlan(channel_axis=-1, channel_group_size=4,
                        spatial_block_size=30, n_channels=17,
                        spatial_extent=77)
        lo = rng.normal(-3, 0.1,
                        (plan.n_cgroups, plan.n_sblocks)).astype(np.float32)
        hi = lo + rng.uniform(1, 5, lo.shape).astype(np.float32)
        lv = np.sort(rng.normal(0, 2, (plan.n_tiles, n_levels))
                     .astype(np.float32), axis=1)
        te = TileECSQ(levels=lv, thresholds=(lv[:, :-1] + lv[:, 1:]) / 2)
        spec = QuantSpec(lo, hi, n_levels, -1, te, plan)
        xj = jnp.asarray(x)
        ij, dj = (np.asarray(a) for a in jb.quantize_dequantize(xj, spec))
        ik, dk = (np.asarray(a) for a in kb.quantize_dequantize(xj, spec))
        np.testing.assert_array_equal(ij, ik)
        np.testing.assert_array_equal(dj, dk)

    def test_designed_tile_ecsq_through_kernel_codec(self):
        """End-to-end: per-tile ECSQ designed by calibrate, quantized via
        the kernel backend, stream round trip bit-exact."""
        rng = np.random.default_rng(2)
        mu = np.linspace(0.0, 5.0, 8).astype(np.float32)
        x = (mu[None] + rng.exponential(1.0, (512, 8))).astype(np.float32)
        cfg = CodecConfig(n_levels=4, use_ecsq=True, clip_mode="minmax",
                          constrain_cmin_zero=False, granularity="channel",
                          channel_axis=-1, backend="kernel_interpret")
        codec = calibrate(cfg, samples=x)
        out = codec.decode(codec.encode(x), shape=x.shape)
        ref_cfg = CodecConfig(n_levels=4, use_ecsq=True, clip_mode="minmax",
                              constrain_cmin_zero=False,
                              granularity="channel", channel_axis=-1,
                              backend="jnp")
        ref = calibrate(ref_cfg, samples=x)
        np.testing.assert_array_equal(out,
                                      ref.decode(ref.encode(x),
                                                 shape=x.shape))
