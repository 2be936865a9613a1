"""The check that decides ``correct``, driven through a whole run on the
CPU at a small size: the harness's look for a chip is skipped, the rest of
a run (set-up, first chunk, window, reference, comparison) is the one the
chip runs.  A sound run passes under the cell's limits; the control (the
reference in float8 in the program's place) and each fault planted under
the timed path fail."""
import json
import time

import pytest

import faults
import harness

CELL = "rwkv6-3b.c512"


def small_cell():
    """The cell's own files at a small width and depth, in float32."""
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    entry = next(w for w in spec["workloads"] if w["name"] == CELL)
    model = harness.load_json(harness.BENCH / "configs" /
                              f"{entry['config']}.json")
    model.update(hidden_size=128, intermediate_size=256,
                 num_hidden_layers=2, vocab_size=512, torch_dtype="float32")
    load = harness.load_json(harness.BENCH / "traffic" /
                             f"{entry['traffic']}.json")
    load.update(seq_len=16, batch_per_chip=4, pool_chunks=2)
    return {**entry, "model": model, "load": load, "end_to_end": [],
            "per_layer": [],
            "limits": harness.load_json(harness.BENCH / "limits" /
                                        f"{CELL}.json")}


def run_once(cell, breakage=None, control=None):
    return harness.execute(cell, 2 ** 31 + 77, 0.5, False,
                           time.perf_counter(), breakage=breakage,
                           control=control, log=lambda m: None)


def test_sound_run_is_correct():
    r = run_once(small_cell())
    assert r["correct"], r["check"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"tokens_per_s", "peak_hbm_gib", "setup_s"}


@pytest.mark.parametrize("control", sorted(harness.CONTROLS))
def test_control_is_not_correct(control):
    r = run_once(small_cell(), control=control)
    assert not r["correct"], r["check"]


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_planted_fault_is_not_correct(fault):
    r = run_once(small_cell(), breakage=faults.FAULTS[fault])
    assert not r["correct"], r["check"]
