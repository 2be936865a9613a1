"""The benchmark's own tests run on the CPU: ``python -m pytest
chipbench/tests`` from the repository root (they sit outside the
repository's testpaths)."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import pytest  # noqa: E402

DATA = BENCH / "tests" / "data"


@pytest.fixture
def data_cell():
    """A cell built from a configuration file of ``tests/data`` and the
    rwkv6-3b.c512 traffic at a test size, without the Pallas kernels:
    ``data_cell(config, **traffic or optimizer overrides)``."""
    import harness

    def make(config, **load):
        model = harness.load_json(DATA / f"{config}.json")
        traffic = harness.load_json(BENCH / "traffic" / "c512.json")
        traffic.update(seq_len=16, batch_per_chip=2, chunk=1, pool_chunks=2)
        traffic["optimizer"] = dict(traffic["optimizer"], use_pallas=False)
        for k, v in load.items():
            (traffic["optimizer"] if k in traffic["optimizer"]
             else traffic)[k] = v
        return {"name": f"{config}.test", "config": config,
                "traffic": "test", "chips": 1, "model": model,
                "load": traffic, "limits": {}, "end_to_end": [],
                "per_layer": []}
    return make
