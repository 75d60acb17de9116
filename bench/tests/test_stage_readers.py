"""The readers of the program's stage spans and of the split halves'
device time, on hand-made runs."""

import pytest

from benchkit import registry


def span(stage, t, dur, **attrs):
    return {"stage": stage, "t_start": t, "dur_s": dur, **attrs}


def run_of(spans=(), steps=4, op_seconds=None):
    return {
        "crossings": [
            {"kind": "prefill", "wall0": 10.0, "wall1": 12.0},
            {"kind": "decode", "wall0": 13.0, "wall1": 13.1},
            {"kind": "decode", "wall0": 13.2, "wall1": 13.3},
        ],
        "spans": list(spans),
        "counters": {"steps": steps},
        "trace": None if op_seconds is None else {"op_seconds": op_seconds},
    }


def read(name, run):
    return registry.metric_reader(name).read(run)


def test_round_trip_stages_go_to_their_crossing():
    run = run_of([
        span("edge_send", 10.1, 0.8), span("result_wait", 10.9, 1.05),
        span("edge_send", 13.01, 0.03), span("result_wait", 13.04, 0.05),
        span("edge_send", 13.21, 0.01), span("result_wait", 13.22, 0.07),
        # outside every crossing: counted nowhere
        span("edge_send", 12.5, 9.0), span("result_wait", 13.15, 9.0),
    ])
    assert read("edge_send_ms.prefill", run) == pytest.approx(800.0)
    assert read("result_wait_ms.prefill", run) == pytest.approx(1050.0)
    assert read("edge_send_ms.decode", run) == pytest.approx(20.0)
    assert read("result_wait_ms.decode", run) == pytest.approx(60.0)


def test_step_host_time_of_decode_kind_over_steps():
    run = run_of([
        span("split_head", 13.0, 0.004, kind="decode"),
        span("split_tail", 13.1, 0.002, kind="decode"),
        span("token_read", 13.11, 0.006, kind="decode"),
        # other kinds and other stages do not count
        span("split_head", 10.0, 1.0, kind="prefill"),
        span("token_read", 12.0, 1.0, kind="prefill"),
        span("split_roundtrip", 13.0, 1.0, kind="decode"),
    ], steps=2)
    assert read("step_host_ms.decode", run) == pytest.approx(6.0)


@pytest.mark.parametrize("name", [
    "edge_send_ms.prefill", "edge_send_ms.decode", "result_wait_ms.prefill",
    "result_wait_ms.decode", "step_host_ms.decode"])
def test_none_without_the_spans(name):
    # a program without these spans (the entropy coder's are not them)
    run = run_of([span("entropy_encode", 13.0, 0.01),
                  span("split_head", 13.0, 0.01)])
    if name == "step_host_ms.decode":
        run["spans"] = [span("split_head", 13.0, 0.01, kind="prefill")]
    assert read(name, run) is None
    assert read(name, run_of()) is None


def test_halves_device_time_by_program_prefix():
    ops = {"jit_decode_to_boundary/fusion.1": 0.004,
           "jit_decode_to_boundary/bitcast_while_fusion.2": 0.006,
           "jit_decode_from_boundary/dot.3": 0.010,
           # a loop's event spans its body's ops, listed on their own
           "jit_decode_to_boundary/while.2": 0.020,
           "jit_decode_from_boundary/conditional": 0.020,
           # not a decode half: neither a prefill half, nor a longer
           # program name that shares the prefix, nor the megakernel
           "jit_prefill_to_boundary/while.2": 1.0,
           "jit_decode_to_boundary_x/fusion": 1.0,
           "jit__encode_fused_flat/_encode_fused_flat.1": 1.0}
    assert read("halves_device_ms.decode",
                run_of(steps=5, op_seconds=ops)) == pytest.approx(4.0)


def test_halves_none_without_named_programs():
    ops = {"jit__lambda/while.2": 0.7, "jit__lambda/slice.15": 0.39}
    assert read("halves_device_ms.decode",
                run_of(op_seconds=ops)) is None
    assert read("halves_device_ms.decode", run_of()) is None
    assert read("halves_device_ms.decode",
                run_of(steps=0, op_seconds={
                    "jit_decode_to_boundary/f": 1.0})) is None
