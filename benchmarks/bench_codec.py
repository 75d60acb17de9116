"""Host-codec throughput micro-benchmark (the ISSUE-1/3/4 gates).

Measures, on a 1M-element float32 activation tensor drawn from the
ResNet-50 layer-21 model:

  * seed bit-serial CABAC encode/decode (``encode_indices_serial``),
  * vectorized rANS entropy encode/decode (``mode="rans"``) and the
    resulting speedups + Melem/s (acceptance: encode >= 20 Melem/s and
    >= 20x serial on both encode and decode),
  * the *fused* end-to-end encode path (``codec.encode`` -- one fused
    quantize pass feeding the entropy stage) vs the unfused reference
    path, asserted byte-identical,
  * a fused-vs-unfused kernel micro-bench (interpret mode): the encode
    megakernel's one pass against separate clip+quant / pack / histogram
    dispatches, with indices asserted bit-identical,
  * compressed bits/element of both coders (the measured rate cost of
    the speed-tuned lane count),
  * per-channel vs per-tensor bits/element at equal N on channel-biased
    benchmark activations (acceptance: channel <= tensor),
  * the tiled-RD sweep: per-tensor vs TilePlan (channel-group x
    spatial-block, v3 streams) measured bits/element *and* MSE at equal N
    (acceptance: tiled MSE below per-tensor at equal-or-lower measured
    bpe for >= 2 level counts),
  * the conv-shaped 2-D RD sweep: flat spatial blocking (v3) vs 2-D
    row x column tiles (v4) on a (1, 64, 56, 56) feature map at equal
    tile count and N (acceptance: 2-D bpe <= flat at equal-or-lower MSE
    for >= 2 level counts),
  * chunked stream encode *and decode* with per-chunk dispatch vs the
    batched rANS loops (``encode_planes_batch`` / ``decode_indices_batch``),
  * the device-resident entropy stage (entropy coder id 4): fused e2e
    encode with ``device_entropy=True`` vs the host coder same-run on a
    sparse serving-like tensor, the bytes-only D2H payload vs the packed
    index tensor the host path fetches, and the dispatch-all/finalize-all
    overlap gain (acceptance: device e2e >= 1.3x the dense host-entropy
    fused e2e the committed baseline records, and >= 4x D2H payload
    reduction, at 1M elements -- both boolean-gated).

Timing takes the best of ``_REPS`` runs (standard micro-bench practice;
the committed numbers must not depend on scheduler noise).  Writes
``BENCH_codec.json`` next to the repo root and prints the CSV rows used
by ``benchmarks/run.py``; ``benchmarks/check_perf_regression.py``
compares the JSON against the committed baseline in CI.

    PYTHONPATH=src python -m benchmarks.bench_codec [--quick]
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

from repro.core import CodecConfig, calibrate
from repro.core import cabac
from repro.core.distributions import resnet50_layer21_model
from repro.core.rate_model import estimated_bits_np

_REPS = 3


def _best(fn, reps: int = _REPS) -> float:
    """Best-of-``reps`` wall time of ``fn`` (first call included)."""
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _biased_channel_features(n_rows: int = 16384, n_channels: int = 64,
                             seed: int = 1) -> np.ndarray:
    """Channel-minor (NHWC-style) features with per-channel bias, the
    BN+ReLU-like case the companion paper's tiled coding targets."""
    rng = np.random.default_rng(seed)
    mu = np.linspace(0.0, 10.0, n_channels).astype(np.float32)
    return (mu[None, :]
            + rng.exponential(1.0, (n_rows, n_channels))).astype(np.float32)


def _conv_features(c: int = 64, h: int = 56, w: int = 56,
                   seed: int = 7) -> np.ndarray:
    """(1, C, H, W) conv feature map with genuine row x column structure
    (an off-center activation blob plus a column ramp, per-channel
    scaled) -- the case arXiv 1804.09963 tiles feature maps spatially
    for.  Flat spatial blocking smears the column structure across
    tiles; 2-D (bh, bw) tiles keep it."""
    rng = np.random.default_rng(seed)
    yy, xx = np.meshgrid(np.arange(h), np.arange(w), indexing="ij")
    blob = 6.0 * np.exp(-(((yy - 20) ** 2) + ((xx - 34) ** 2))
                        / (2 * 12.0 ** 2))
    ramp = np.linspace(0.0, 2.5, w)[None, :]
    mu = (blob + ramp).astype(np.float32)
    ch = np.linspace(0.5, 2.0, c).astype(np.float32)
    x = ch[:, None, None] * mu[None] \
        + rng.exponential(0.5, (c, h, w)).astype(np.float32)
    return x[None].astype(np.float32)


def _bench_fused_kernel_micro() -> dict:
    """Megakernel (one pass) vs separate clip+quant / pack / histogram
    dispatches, in interpret mode on a small tensor; asserts the fused
    coded indices match the unfused kernel path bit-exactly."""
    import jax.numpy as jnp
    from repro.core.backend import get_backend, QuantSpec

    kb = get_backend("kernel_interpret")
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(2, 3, (1 << 16,)).astype(np.float32))
    spec = QuantSpec(0.0, 9.0, 4)

    def fused():
        coded, hists = kb.encode_fused(x, spec, 2, want_hist=True)
        return coded, hists

    def unfused():
        idx = kb.quantize(x, spec)
        packed = np.asarray(kb.pack_indices(idx, 2))
        hist = np.asarray(kb.histogram(idx, 4))
        return np.asarray(idx), packed, hist

    t_fused = _best(lambda: fused())
    t_unfused = _best(lambda: unfused())
    coded, hists = fused()
    idx, _, hist = unfused()
    if not np.array_equal(coded, idx.ravel()):
        raise RuntimeError("fused megakernel indices != unfused kernel path")
    if not np.array_equal(hists.ravel(), hist):
        raise RuntimeError("fused megakernel histogram != histogram kernel")
    return {
        "kernel_fused_s": t_fused,
        "kernel_unfused_s": t_unfused,
        "kernel_fused_vs_unfused": t_unfused / t_fused,
        "kernel_fused_identical": True,
    }


def _bench_device_entropy(n: int, baseline_fused_melem_s: float) -> dict:
    """Device-resident entropy stage (coder id 4) on a serving-like
    sparse activation tensor (ReLU'd boundary features are mostly zero
    -- the regime split inference actually ships, where the bit-plane
    coder's work tracks the live suffix rather than the tensor size).

    The headline gate compares the device-entropy fused e2e throughput
    against ``baseline_fused_melem_s`` -- the dense host-entropy fused
    e2e measured in the *same run* (the quantity the committed baseline
    records, so the ratio is hardware-normalized): the claim is that
    on-device coding in the serving regime clears the throughput cap the
    host entropy stage imposed.  The same-tensor host-vs-device ratio is
    also recorded (``device_entropy_speedup``): on a CPU-only box both
    stages run on the same silicon and there is no bus to save, so that
    ratio sits near 1.0 and the structural win shows up in the D2H
    payload reduction instead (coded bytes vs the packed index tensor
    the host path fetches -- the number that turns into wall-clock on a
    real accelerator link and is counted by
    ``repro_codec_d2h_bytes_total``)."""
    import jax.numpy as jnp

    from repro.kernels import rans_coder

    rng = np.random.default_rng(11)
    x = rng.exponential(1.0, n).astype(np.float32)
    x[rng.random(n) < 0.97] = 0.0
    codec = calibrate(CodecConfig(n_levels=4, clip_mode="minmax",
                                  constrain_cmin_zero=False), samples=x)
    bits = codec.bits_per_index()

    host_blob = codec.encode(x)                       # warms the host jit
    dev_blob = codec.encode(x, device_entropy=True)   # warms the device jit
    identical = np.array_equal(
        np.asarray(codec.decode(dev_blob, shape=x.shape)),
        np.asarray(codec.decode(host_blob, shape=x.shape)))
    if not identical:
        raise RuntimeError("device-entropy stream decoded differently "
                           "from the host stream")
    t_host = _best(lambda: codec.encode(x))
    t_dev = _best(lambda: codec.encode(x, device_entropy=True))

    # D2H payload: the host fused path fetches the packed index tensor
    # (bits/8 bytes per element); the device path's bytes-only fetches
    # are counted by repro_codec_d2h_bytes_total at the fetch site
    host_d2h = n * bits // 8
    ctr = rans_coder._d2h_counter()
    v0 = ctr.value()
    codec.encode(x, device_entropy=True)
    dev_d2h = int(ctr.value() - v0)
    d2h_reduction = host_d2h / max(dev_d2h, 1)

    # overlap gain: dispatch all chunk stages before draining any D2H
    # (the serving-tick shape) vs a strict dispatch+finalize per chunk
    coded = codec.backend.coded_indices_device(
        jnp.asarray(x), codec.spec(), bits)
    n_chunks = 8
    step = -(-n // n_chunks)
    bounds = [(i * step, min((i + 1) * step, n)) for i in range(n_chunks)]

    def sequential():
        return [rans_coder.finalize_index_chunks(
            rans_coder.dispatch_index_chunks(coded, 4, [b]))[0]
            for b in bounds]

    def overlapped():
        return rans_coder.finalize_index_chunks(
            rans_coder.dispatch_index_chunks(coded, 4, bounds))

    if sequential() != overlapped():
        raise RuntimeError("overlapped dispatch changed the chunk bytes")
    t_seq = _best(sequential)
    t_olap = _best(overlapped)

    dev_melem_s = n / t_dev / 1e6
    vs_baseline = dev_melem_s / baseline_fused_melem_s
    return {
        "sparsity": 0.97,
        "host_fused_e2e_s": t_host,
        "device_fused_e2e_s": t_dev,
        "host_fused_Melem_per_s": n / t_host / 1e6,
        "device_fused_Melem_per_s": dev_melem_s,
        "device_entropy_speedup": t_host / t_dev,
        "device_e2e_vs_baseline_fused": vs_baseline,
        "device_e2e_ge_1_3x_baseline": vs_baseline >= 1.3,
        "host_d2h_bytes": host_d2h,
        "device_d2h_bytes": dev_d2h,
        "d2h_reduction": d2h_reduction,
        "device_d2h_reduction_ge_4x": d2h_reduction >= 4.0,
        "device_overlap_gain": t_seq / t_olap,
        "device_stream_identical": identical,
    }


def bench_codec(quick: bool = False) -> list[str]:
    n = 1 << 18 if quick else 1_000_000
    m = resnet50_layer21_model()
    feats = m.sample(n, np.random.default_rng(0)).astype(np.float32)
    codec = calibrate(CodecConfig(n_levels=4, clip_mode="model"),
                      samples=feats[:100_000])
    idx = np.asarray(codec.quantize(feats))

    t_enc_serial = _best(
        lambda: cabac.encode_indices_serial(idx, 4), reps=1)
    blob_serial = cabac.encode_indices_serial(idx, 4)
    t_dec_serial = _best(
        lambda: cabac.decode_indices_serial(blob_serial, idx.size, 4),
        reps=1)
    assert (cabac.decode_indices_serial(blob_serial, idx.size, 4)
            == idx).all()

    t_enc_rans = _best(lambda: cabac.encode_indices(idx, 4, mode="rans"))
    blob_rans = cabac.encode_indices(idx, 4, mode="rans")
    t_dec_rans = _best(
        lambda: cabac.decode_indices(blob_rans, idx.size, 4))
    assert (cabac.decode_indices(blob_rans, idx.size, 4) == idx).all()

    # fused end-to-end encode (x -> wire bytes, one fused quantize pass)
    # vs the unfused reference path -- byte-identical by construction
    t_enc_fused = _best(lambda: codec.encode(feats))
    t_enc_unfused = _best(lambda: codec.encode(feats, fused=False))
    blob_fused = codec.encode(feats)
    fused_identical = blob_fused == codec.encode(feats, fused=False)
    if not fused_identical:
        raise RuntimeError("fused encode is not byte-identical to the "
                           "unfused reference path")
    t_dec_e2e = _best(lambda: codec.decode(blob_fused, shape=feats.shape))

    enc_speedup = t_enc_serial / t_enc_rans
    dec_speedup = t_dec_serial / t_dec_rans
    bpe_serial = 8 * len(blob_serial) / idx.size
    bpe_rans = 8 * len(blob_rans) / idx.size
    bpe_entropy = estimated_bits_np(idx, 4) / idx.size

    micro = _bench_fused_kernel_micro()

    # per-channel vs per-tensor rate at equal N on biased-channel features
    xc = _biased_channel_features()
    common = dict(clip_mode="minmax", constrain_cmin_zero=False)
    grain_bpe = {}
    tensor_codecs = {}
    for n_levels in (2, 4, 8):
        tn = calibrate(CodecConfig(n_levels=n_levels, **common), samples=xc)
        tensor_codecs[n_levels] = tn
        ch = calibrate(CodecConfig(n_levels=n_levels, granularity="channel",
                                   channel_axis=-1, **common), samples=xc)
        grain_bpe[n_levels] = {
            "tensor": tn.compressed_bits_per_element(xc),
            "channel": ch.compressed_bits_per_element(xc),
        }

    # tiled-RD sweep: channel-group x spatial-block TilePlan (v3 streams)
    # vs per-tensor at equal N -- measured wire bpe (header included) + MSE
    # (the per-tensor codecs/rates are reused from the granularity loop)
    import jax.numpy as jnp
    xj = jnp.asarray(xc)
    tiled_rd = {}
    for n_levels in (2, 4, 8):
        tn = tensor_codecs[n_levels]
        tl = calibrate(CodecConfig(n_levels=n_levels, granularity="tile",
                                   channel_axis=-1, channel_group_size=2,
                                   spatial_block_size=4096, **common),
                       samples=xc)
        tiled_rd[n_levels] = {
            "tensor_bpe": grain_bpe[n_levels]["tensor"],
            "tensor_mse": float(np.mean(
                (np.asarray(tn.apply(xj)) - xc) ** 2)),
            "tile_bpe": tl.compressed_bits_per_element(xc),
            "tile_mse": float(np.mean(
                (np.asarray(tl.apply(xj)) - xc) ** 2)),
        }
    rd_wins = sum(1 for v in tiled_rd.values()
                  if v["tile_bpe"] <= v["tensor_bpe"]
                  and v["tile_mse"] < v["tensor_mse"])

    # conv-shaped 2-D RD sweep: a (1, 64, 56, 56) NCHW map whose stats
    # drift along rows AND columns.  2-D (8, 8) row x column tiles (v4
    # streams) vs flat 64-element spatial blocking (v3) at the *same*
    # tile count (49 spatial blocks either way, so equal side-info), at
    # equal N -- measured wire bpe (header included) + MSE
    import jax.numpy as _jnp
    xconv = _conv_features()
    xconv_j = _jnp.asarray(xconv)
    conv_common = dict(clip_mode="minmax", constrain_cmin_zero=False,
                       granularity="tile", channel_axis=1,
                       channel_group_size=8)
    conv2d_rd = {}
    for n_levels in (2, 4, 8):
        flat = calibrate(CodecConfig(n_levels=n_levels,
                                     spatial_block_size=64, **conv_common),
                         samples=xconv)
        t2d = calibrate(CodecConfig(n_levels=n_levels,
                                    spatial_block_hw=(8, 8), **conv_common),
                        samples=xconv)
        conv2d_rd[n_levels] = {
            "flat_bpe": flat.compressed_bits_per_element(xconv),
            "flat_mse": float(np.mean(
                (np.asarray(flat.apply(xconv_j)) - xconv) ** 2)),
            "tile2d_bpe": t2d.compressed_bits_per_element(xconv),
            "tile2d_mse": float(np.mean(
                (np.asarray(t2d.apply(xconv_j)) - xconv) ** 2)),
        }
    conv2d_wins = sum(1 for v in conv2d_rd.values()
                      if v["tile2d_bpe"] <= v["flat_bpe"]
                      and v["tile2d_mse"] <= v["flat_mse"])

    # chunked stream encode + decode: per-chunk dispatch vs the batched
    # rANS loops on both sides
    stream_codec = calibrate(CodecConfig(n_levels=4, clip_mode="model"),
                             samples=feats[:100_000])
    # 2^16-element chunks keep every chunk above the serial-coder cutoff
    # (so the batched rANS loops are what gets measured) in --quick too
    chunk = 1 << 16
    n_payloads = sum(1 for _ in stream_codec.encode_stream(
        feats, chunk_elems=chunk))
    t_stream_serial = _best(lambda: sum(1 for _ in stream_codec.encode_stream(
        feats, chunk_elems=chunk, chunk_batch=1)))
    t_stream_batch = _best(lambda: sum(1 for _ in stream_codec.encode_stream(
        feats, chunk_elems=chunk)))
    payloads = list(stream_codec.encode_stream(feats, chunk_elems=chunk))

    from repro.core import ChunkStreamDecoder

    def decode_stream_with(batch: int) -> np.ndarray:
        dec = ChunkStreamDecoder(payloads[0], chunk_batch=batch)
        for p in payloads[1:]:
            dec.add_chunk(p)
        return dec.finish()

    t_sdec_perchunk = _best(lambda: decode_stream_with(1))
    t_sdec_batch = _best(lambda: decode_stream_with(
        len(payloads)))  # fully batched: one loop per TU round
    np.testing.assert_array_equal(decode_stream_with(1),
                                  decode_stream_with(len(payloads)))

    device = _bench_device_entropy(n, feats.size / t_enc_fused / 1e6)

    result = {
        "n_elements": int(idx.size),
        "encode_serial_s": t_enc_serial,
        "decode_serial_s": t_dec_serial,
        "encode_rans_s": t_enc_rans,
        "decode_rans_s": t_dec_rans,
        "encode_fused_s": t_enc_fused,
        "encode_unfused_s": t_enc_unfused,
        "decode_e2e_s": t_dec_e2e,
        "fused_identical": fused_identical,
        "encode_speedup": enc_speedup,
        "decode_speedup": dec_speedup,
        "encode_speedup_ge_20x": enc_speedup >= 20.0,
        "decode_speedup_ge_20x": dec_speedup >= 20.0,
        "encode_Melem_per_s": idx.size / t_enc_rans / 1e6,
        "decode_Melem_per_s": idx.size / t_dec_rans / 1e6,
        "fused_encode_Melem_per_s": feats.size / t_enc_fused / 1e6,
        "e2e_decode_Melem_per_s": feats.size / t_dec_e2e / 1e6,
        "bits_per_elem_serial": bpe_serial,
        "bits_per_elem_rans": bpe_rans,
        "bits_per_elem_entropy_bound": bpe_entropy,
        **micro,
        "granularity_bits_per_elem": grain_bpe,
        "channel_le_tensor": all(v["channel"] <= v["tensor"]
                                 for v in grain_bpe.values()),
        "tiled_rd": tiled_rd,
        "tiled_rd_wins": rd_wins,
        "tiled_beats_tensor_ge_2_levels": rd_wins >= 2,
        "conv2d_rd": conv2d_rd,
        "conv2d_rd_wins": conv2d_wins,
        "conv2d_beats_flat_ge_2_levels": conv2d_wins >= 2,
        "stream_chunk_elems": chunk,
        "stream_encode_perchunk_s": t_stream_serial,
        "stream_encode_batched_s": t_stream_batch,
        "stream_batch_speedup": t_stream_serial / t_stream_batch,
        "stream_decode_perchunk_s": t_sdec_perchunk,
        "stream_decode_batched_s": t_sdec_batch,
        "stream_decode_batch_speedup": t_sdec_perchunk / t_sdec_batch,
        "device_entropy": device,
    }
    with open("BENCH_codec.json", "w") as f:
        json.dump(result, f, indent=2)

    rows = [
        f"codec_encode_serial,{t_enc_serial*1e6:.0f},"
        f"Melem_s={idx.size/t_enc_serial/1e6:.3f},bpe={bpe_serial:.3f}",
        f"codec_encode_rans,{t_enc_rans*1e6:.0f},"
        f"Melem_s={idx.size/t_enc_rans/1e6:.1f},bpe={bpe_rans:.3f},"
        f"speedup={enc_speedup:.1f}x",
        f"codec_decode_rans,{t_dec_rans*1e6:.0f},"
        f"Melem_s={idx.size/t_dec_rans/1e6:.1f},speedup={dec_speedup:.1f}x",
        f"codec_encode_fused_e2e,{t_enc_fused*1e6:.0f},"
        f"Melem_s={feats.size/t_enc_fused/1e6:.1f},"
        f"vs_unfused={t_enc_unfused/t_enc_fused:.2f}x,identical=True",
        f"codec_kernel_fused_micro,{micro['kernel_fused_s']*1e6:.0f},"
        f"vs_separate={micro['kernel_fused_vs_unfused']:.2f}x",
    ]
    for n_levels, v in grain_bpe.items():
        rows.append(f"codec_granularity_N{n_levels},0,"
                    f"bpe_tensor={v['tensor']:.3f},"
                    f"bpe_channel={v['channel']:.3f}")
    for n_levels, v in tiled_rd.items():
        rows.append(f"codec_tiled_rd_N{n_levels},0,"
                    f"tensor_bpe={v['tensor_bpe']:.3f},"
                    f"tensor_mse={v['tensor_mse']:.4f},"
                    f"tile_bpe={v['tile_bpe']:.3f},"
                    f"tile_mse={v['tile_mse']:.4f}")
    for n_levels, v in conv2d_rd.items():
        rows.append(f"codec_conv2d_rd_N{n_levels},0,"
                    f"flat_bpe={v['flat_bpe']:.3f},"
                    f"flat_mse={v['flat_mse']:.4f},"
                    f"tile2d_bpe={v['tile2d_bpe']:.3f},"
                    f"tile2d_mse={v['tile2d_mse']:.4f}")
    rows.append(f"codec_stream_encode_batched,{t_stream_batch*1e6:.0f},"
                f"chunks={n_payloads - 1},"
                f"vs_perchunk={t_stream_serial/t_stream_batch:.2f}x")
    rows.append(f"codec_stream_decode_batched,{t_sdec_batch*1e6:.0f},"
                f"chunks={n_payloads - 1},"
                f"vs_perchunk={t_sdec_perchunk/t_sdec_batch:.2f}x")
    rows.append(f"codec_device_entropy_e2e,"
                f"{device['device_fused_e2e_s']*1e6:.0f},"
                f"Melem_s={device['device_fused_Melem_per_s']:.1f},"
                f"vs_baseline_fused="
                f"{device['device_e2e_vs_baseline_fused']:.2f}x,"
                f"d2h_reduction={device['d2h_reduction']:.1f}x,"
                f"overlap_gain={device['device_overlap_gain']:.2f}x")
    return rows


def main() -> None:
    quick = "--quick" in sys.argv
    print("name,us_per_call,derived")
    for row in bench_codec(quick=quick):
        print(row, flush=True)


if __name__ == "__main__":
    main()
