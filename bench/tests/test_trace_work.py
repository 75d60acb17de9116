"""Trace reduction, the table of peaks and the work counts."""

import glob
from pathlib import Path

import pytest

from benchkit import peaks, registry, trace, work

DATA = Path(__file__).with_name("data")


def test_layer_params_by_hand():
    q = registry.config("codeqwen1.5-7b")
    # q, o: 4096 x 32 x 128; k, v: 4096 x 4 x 128; MLP: 3 x 4096 x 13440
    assert work.layer_params(q) == (2 * 16_777_216 + 2 * 2_097_152
                                    + 165_150_720)
    # 32 published layers and the untied embedding and head give the
    # model card's 7.25 B parameters (norms aside)
    total = 32 * work.layer_params(q) + 2 * 92_416 * 4096
    assert round(total / 1e9, 2) == 7.25
    assert work.head_flops(q) == 2 * 4096 * 92_416


def test_request_flops_by_hand():
    q = registry.config("codeqwen1.5-7b")
    # prompt of 2, 2 tokens served: positions 0, 1, 2 enter 4 layers
    # (2 x 202,899,456 each), attend to 1 + 2 + 3 keys (4 x 32 x 128 a
    # key a layer), and 2 positions reach the head
    dense = 3 * 2 * 202_899_456 * 4
    attn = (1 + 2 + 3) * 4 * 32 * 128 * 4
    head = 2 * 2 * 4096 * 92_416
    assert work.request_flops(q, 2, 2) == dense + attn + head
    # one-shot: the prompt and one head position
    assert work.request_flops(q, 1, 1) == 2 * 202_899_456 * 4 \
        + 4 * 32 * 128 * 4 + 2 * 4096 * 92_416
    assert work.request_flops(q, 5, 0) == 0


def test_encode_bytes():
    # a decode-step tensor (4, 1, 4096): bf16 read + 2-bit indices
    assert work.encode_bytes(16_384, 2) == 16_384 * 2.25


def test_peaks_table():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError):
        peaks.peaks("TPU v9 imaginary")


def test_reduce_events_by_hand():
    ev = trace.Events(
        device={"/device:TPU:0": [("a", 10, 30), ("b", 20, 40),
                                  ("a", 60, 70), ("c", 90, 130)]},
        host=[("bench.window", 0, 100), ("bench.roundtrip", 40, 60),
              ("bench.wave", 0, 100)])
    r = trace.reduce_events(ev, (0, 100), ("bench.roundtrip", "bench.wave"))
    # busy: [10, 40] + [60, 70] + [90, 100] = 50 ns of a 100 ns window
    assert r["busy_s"] == pytest.approx(50e-9)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["op_seconds"] == pytest.approx(
        {"a": 30e-9, "b": 20e-9, "c": 10e-9})
    # idle [0, 10] and [70, 90] under the wave, [40, 60] in the round trip
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"bench.wave": 30e-9, "bench.roundtrip": 20e-9})
    assert r["device_ops"][0][0] == "a"


def test_union_length():
    assert trace.union_length([(0, 5), (3, 8), (10, 12)]) == 10


def test_recorded_trace():
    """A trace recorded on one v5e: the fused encode of a (4, 64, 4096)
    tensor and a bf16 matmul, inside ``bench.window``."""
    paths = glob.glob(str(DATA / "*.xplane.pb"))
    assert paths, "recorded trace missing"
    ev = trace.load_xplane(paths[0])
    w = trace.window_of(ev, "bench.window")
    r = trace.reduce_events(ev, w, ("bench.roundtrip",))
    assert r["devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    assert sum(r["op_seconds"].values()) >= r["busy_s"] * (1 - 1e-9)
    assert "jit__encode_fused_flat/_encode_fused_flat.1" in r["op_seconds"]
    assert any(k.startswith("jit_matmul/") for k in r["op_seconds"])
    assert dict(r["idle_gaps"]).keys() <= {"bench.roundtrip", "other"}
