"""The reduction from a trace to the per-layer metrics, on hand-made event
lists and on a trimmed copy of a traced run of the cell on a TPU v5e
(``data/``), recorded by ``tracefile.save``."""
from pathlib import Path

import pytest

import harness
import tracefile

DATA = Path(__file__).resolve().parent / "data"
RECORDED = DATA / "rwkv6-3b.s1024.trace.json.gz"


def events(ops, host=()):
    return {"devices": {"0": [list(o) for o in ops]},
            "host": [list(h) for h in host]}


def test_interval_algebra():
    assert tracefile.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == \
        [[0, 3], [5, 8]]
    assert tracefile.subtract([[0, 10]], [[2, 3], [5, 12]]) == \
        [[0, 2], [3, 5]]
    assert tracefile.clip([[0, 4], [6, 9]], 2, 7) == [[2, 4], [6, 7]]


SMW = ('%vmap__.9 = bf16[2,256,256]{2,1,0} custom-call(bf16[2,256,256]'
       '{2,1,0} %g.1, f32[2,256,1]{2,1,0} %g.2, f32[2,256,1]{2,1,0} %g.2), '
       'custom_call_target="tpu_custom_call"')
MATMUL = ('%vmap__.8 = f32[2,256,64]{2,1,0} custom-call(bf16[2,256,256]'
          '{2,1,0} %g.1, bf16[2,256,64]{2,1,0} %g.3), '
          'custom_call_target="tpu_custom_call"')
FUSION = "%fusion.1 = f32[4]{0} fusion(f32[4]{0} %p.1), kind=kLoop"
LOOP = "%while.2 = (s32[], f32[4]{0}) while((s32[], f32[4]{0}) %t.1)"


def test_busy_idle_and_gaps_by_hand():
    ev = events([(LOOP, 10, 40), (FUSION, 10, 30), (MATMUL, 20, 30),
                 (SMW, 70, 10)],
                host=[("stack_batches", 0, 10), ("dispatch", 10, 40),
                      ("device_get", 50, 50)])
    busy, window = tracefile.busy_and_window(ev)
    assert window == pytest.approx(100e-9)
    assert busy == pytest.approx(50e-9)            # [10, 50) and [70, 80)
    gaps = tracefile.breakdown(ev)["idle_gaps"]
    assert gaps[0] == ["idle during device_get", pytest.approx(20e-9)]
    assert tracefile.pallas_seconds(ev, "0", smw=True) == \
        pytest.approx(10e-9)
    assert tracefile.pallas_seconds(ev, "0", smw=False) == \
        pytest.approx(30e-9)
    ops = dict(tracefile.breakdown(ev)["device_ops"])
    assert ops == {"fusion.1 (fusion)": pytest.approx(30e-9),
                   "vmap__.8 (tpu_custom_call)": pytest.approx(30e-9),
                   "vmap__.9 (tpu_custom_call)": pytest.approx(10e-9)}


def recorded():
    if not RECORDED.exists():
        pytest.fail(f"recorded trace {RECORDED.name} is missing")
    return tracefile.read(RECORDED)


def test_recorded_trace_reduces():
    ev = recorded()
    busy, window = tracefile.busy_and_window(ev)
    assert 0 < busy <= window
    out = tracefile.breakdown(ev)
    assert 0 < len(out["device_ops"]) <= 10
    assert len(out["idle_gaps"]) <= 10
    assert all(s > 0 for _, s in out["device_ops"])


@pytest.mark.parametrize("metric", ["device_idle_pct", "mfu",
                                    "smw_roofline_pct",
                                    "precond_roofline_pct"])
def test_recorded_trace_metrics_are_shares(metric):
    ev = recorded()
    ctx = dict(ev["ctx"], events=ev, log=lambda m: None)
    mod = harness.load_module(harness.BENCH / "metrics" / f"{metric}.py")
    value = mod.read(ctx)
    assert value is not None and 0 < value <= 100, value

