"""``chip_smoke.py`` on the CPU: it refuses to report without a chip or a
checkout, and its phases pass at smoke width with the Pallas kernels in
interpret mode (the device expectations are steered here, not through
an option of the script)."""

import dataclasses
import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(cwd: Path, script: Path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _assert_no_result(res):
    assert res.returncode != 0
    for line in res.stdout.splitlines():
        assert '"ok"' not in line, res.stdout


def test_fails_without_accelerator():
    res = _run(ROOT, SCRIPT)
    _assert_no_result(res)
    assert "no tpu device" in res.stderr, res.stderr[-2000:]


def test_fails_outside_checkout(tmp_path):
    shutil.copy(SCRIPT, tmp_path / SCRIPT.name)
    res = _run(tmp_path, tmp_path / SCRIPT.name)
    _assert_no_result(res)
    assert "no repro package" in res.stderr, res.stderr[-2000:]


def test_smoke_config_is_published_width(smoke):
    cfg = smoke.smoke_config()
    assert (cfg.d_model, cfg.num_heads, cfg.head_dim, cfg.d_ff,
            cfg.vocab_size, cfg.dtype) == (4096, 32, 128, 13440, 92416,
                                           "bfloat16")
    assert cfg.num_layers == 4
    from repro.models.transformer import build_groups
    groups, boundary = build_groups(cfg, split=True)
    assert boundary == 1 and groups[0].n_periods == 2


def test_phases_at_smoke_width(smoke, monkeypatch, capsys):
    from repro.configs import reduced
    monkeypatch.setattr(smoke, "PLATFORM", "cpu")
    monkeypatch.setattr(smoke, "INTERPRET", True)
    monkeypatch.setenv("REPRO_QUANT_BACKEND", "kernel_interpret")
    assert smoke.device_info()["platform"] == "cpu"
    cfg = dataclasses.replace(reduced(smoke.smoke_config(), layers=4),
                              split_after_period=smoke.SPLIT_AFTER)
    out = smoke.serve_phase(cfg, "tensor", 0)
    chan = smoke.serve_phase(cfg, "channel", 0, params=out["params"])
    x = smoke.boundary_tensor(cfg, out["params"], 0)
    assert x.shape == (smoke.REQUESTS, smoke.PROMPT_LEN, cfg.d_model)
    for codec in (out["codec"], chan["codec"]):
        assert 0.0 < smoke.identity_phase(codec, x) < 8.0
    text = capsys.readouterr().out
    assert text.count("kernel stream == jnp stream") == 4
    assert "host-rANS fallback payloads: 0" in text


def test_backend_check_rejects_jnp(smoke):
    from repro.core import CodecConfig, calibrate
    codec = calibrate(CodecConfig(n_levels=4, clip_mode="manual",
                                  manual_cmin=-1.0, manual_cmax=1.0,
                                  backend="jnp"))
    with pytest.raises(SystemExit, match="not 'kernel'"):
        smoke.check_kernel_backend(codec)


def test_result_line_format(smoke, monkeypatch, capsys):
    """The last line is the JSON object the chip run is read by; with
    the device expectation steered to the CPU, only the phases are
    stubbed."""
    monkeypatch.setattr(smoke, "PLATFORM", "cpu")
    monkeypatch.setattr(smoke, "one_chip", lambda seed: None)
    from repro.launch import compile_cache
    monkeypatch.setattr(compile_cache, "enable_compile_cache",
                        lambda: "unused")
    assert smoke.main([]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    res = json.loads(last)
    assert res["ok"] is True
    assert set(res["device"]) == {"platform", "kind", "count"}
    assert res["device"]["platform"] == "cpu"
