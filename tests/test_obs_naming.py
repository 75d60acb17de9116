"""Instrument naming-convention gate.

Every metric any component registers must follow
``repro_<subsystem>_<name>_<unit>`` (lowercase underscore tokens, a
recognized unit suffix, counters ending ``_total``), so scrape names --
the public telemetry API -- stay stable across PRs.  The live checks
instantiate each instrumented component and validate every instrument it
actually registered; a renamed or malformed instrument fails here before
it ever reaches a dashboard.
"""

import dataclasses
import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from repro.core import CodecConfig, calibrate
from repro.obs import configure_tracing, default_registry
from repro.obs.metrics import ALLOWED_UNITS, validate_name


class TestValidateName:
    @pytest.mark.parametrize("name,kind", [
        ("repro_server_ticks_total", "counter"),
        ("repro_client_coded_bytes_total", "counter"),
        ("repro_engine_ttft_seconds", "histogram"),
        ("repro_rate_target_bpe", "gauge"),
        ("repro_server_queue_depth_count", "gauge"),
        ("repro_pipeline_stage_latency_seconds", "histogram"),
    ])
    def test_accepts(self, name, kind):
        validate_name(name, kind)

    @pytest.mark.parametrize("name,kind", [
        ("server_ticks_total", "counter"),          # missing prefix
        ("repro_Server_ticks_total", "counter"),    # uppercase
        ("repro_ticks", "counter"),                 # too few tokens
        ("repro_server_speed_furlongs", "gauge"),   # unknown unit
        ("repro_server_ticks_count", "counter"),    # counter not _total
        ("repro_server_depth_total", "gauge"),      # _total on non-counter
        ("repro_server__ticks_total", "counter"),   # empty token
    ])
    def test_rejects(self, name, kind):
        with pytest.raises(ValueError):
            validate_name(name, kind)

    def test_units_are_closed_set(self):
        # adding a unit is an API decision: update this list consciously
        assert ALLOWED_UNITS == {"total", "seconds", "bytes", "bits",
                                 "elements", "chunks", "count", "bpe",
                                 "ratio", "info"}


def _assert_conforms(registry, expect_prefixes):
    instruments = registry.instruments()
    assert instruments, "component registered no instruments"
    for inst in instruments:
        validate_name(inst.name, inst.kind)     # raises on violation
        for ln in inst.labelnames:
            assert ln.islower(), (inst.name, ln)
    names = {i.name for i in instruments}
    for prefix in expect_prefixes:
        assert any(n.startswith(prefix) for n in names), \
            f"no {prefix}* instrument in {sorted(names)}"


class TestLiveInstruments:
    def test_server_and_batcher(self):
        from repro.transport import CloudServer
        srv = CloudServer()
        _assert_conforms(srv.metrics, ["repro_server_", "repro_decode_"])

    def test_client_and_rate_controller(self):
        from repro.transport import (CodecBank, RateControlConfig,
                                     RateController)
        from repro.transport.client import EdgeClient
        rng = np.random.default_rng(0)
        samples = rng.exponential(1.0, 4096).astype(np.float32)
        bank = CodecBank(CodecConfig(n_levels=8, clip_mode="minmax",
                                     constrain_cmin_zero=False), samples)
        rc = RateController(RateControlConfig(target_bpe=2.0))
        client = EdgeClient("127.0.0.1", 1, codec_bank=bank,
                            rate_controller=rc)
        _assert_conforms(client.metrics, ["repro_client_", "repro_rate_"])

    def test_engine(self):
        import jax

        from repro.configs import ARCHS, reduced
        from repro.models import init_params
        from repro.serving import ServeEngine
        cfg = dataclasses.replace(reduced(ARCHS["codeqwen1.5-7b"]),
                                  vocab_size=128, d_model=32, d_ff=64,
                                  num_heads=2, num_kv_heads=2, head_dim=16)
        params = init_params(cfg, jax.random.PRNGKey(0))
        eng = ServeEngine(cfg, params, slots=2, max_seq=64)
        _assert_conforms(eng.metrics, ["repro_engine_"])

    def test_default_registry(self):
        # importing rate_control registers the bank-cache instruments;
        # enabling tracing registers the stage-latency histogram
        import repro.transport.rate_control  # noqa: F401
        configure_tracing(enabled=True)
        configure_tracing(enabled=False)
        _assert_conforms(default_registry(),
                         ["repro_bank_cache_", "repro_pipeline_"])
