"""Benchmark tests run on the CPU at smoke width:

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest bench/tests -q

They are not part of the repository's tier-1 suite (``testpaths`` is
``tests``)."""

import copy
import os
import sys
import tempfile
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      tempfile.mkdtemp(prefix="bench_tests_jax_cache_"))

SMOKE_WIDTHS = {"hidden_size": 128, "intermediate_size": 256,
                "num_attention_heads": 4, "num_key_value_heads": 2,
                "head_dim": 32, "vocab_size": 512}


def smoke_spec(cell: str, bench: dict, **traffic) -> dict:
    """The cell's files with the configuration cut to smoke width and the
    traffic to short requests; everything else as committed."""
    from benchkit import registry
    spec = copy.deepcopy(registry.cell(cell, bench))
    spec["conf"].update(SMOKE_WIDTHS)
    spec["mix"].update(traffic)
    spec["workload"]["max_seq"] = (max(spec["mix"]["prompt_buckets"])
                                   + max(spec["mix"]["output_buckets"]) + 8)
    return spec


@pytest.fixture(scope="session")
def bench():
    from benchkit import registry
    return registry.benchmark()
