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


# A compiled program's text as the attribution reads it: each instruction
# with the op_name of its metadata (none on XLA's own copy).
HLO_TEXT = '''
HloModule jit_run_chunk
%body.1 (p: f32[4]) -> f32[4] {
  %fusion.1 = f32[4]{0} fusion(f32[4]{0} %p.1), kind=kLoop, metadata={op_name="jit(run_chunk)/while/body/forward/dot_general" source_file="x.py" source_line=3}
  %fusion.2 = f32[4]{0} fusion(f32[4]{0} %p.1), kind=kLoop, metadata={op_name="jit(run_chunk)/while/body/transpose(jvp(forward))/mul"}
  %vmap__.8 = f32[2,256,64]{2,1,0} custom-call(bf16[2,256,256]{2,1,0} %g.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(run_chunk)/while/body/mkor_precondition/vmap(mkor_matmul)"}
  %vmap__.9 = bf16[2,256,256]{2,1,0} custom-call(bf16[2,256,256]{2,1,0} %g.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(run_chunk)/while/body/cond/branch_1_fun/mkor_smw/vmap(mkor_smw)"}
  %copy.3 = f32[4]{0} copy(f32[4]{0} %p.1)
  ROOT %add.4 = f32[4]{0} add(f32[4]{0} %p.1, f32[4]{0} %p.1), metadata={op_name="jit(run_chunk)/while/body/backend/mkor_stats/add"}
}
ENTRY %main.5 (a: f32[4]) -> f32[4] {
  %while.2 = (s32[], f32[4]{0}) while((s32[], f32[4]{0}) %t.1), condition=%c, body=%body.1, metadata={op_name="jit(run_chunk)/while"}
}
'''


def test_stage_attribution_by_hand():
    names = tracefile.op_names(HLO_TEXT)
    assert "copy.3" not in names and len(names) == 6
    add = "%add.4 = f32[4]{0} add(f32[4]{0} %p.1, f32[4]{0} %p.1)"
    fwd = "%fusion.2 = f32[4]{0} fusion(f32[4]{0} %p.1), kind=kLoop"
    copy = "%copy.3 = f32[4]{0} copy(f32[4]{0} %p.1)"
    ev = events([(LOOP, 0, 100), (FUSION, 0, 10), (fwd, 10, 20),
                 (MATMUL, 30, 30), (SMW, 60, 5), (copy, 65, 7),
                 (add, 72, 3), (FUSION, 80, 10)])
    got = tracefile.stage_seconds(ev, names)
    # the loop is left out; the forward op under transpose( is backward;
    # the innermost stage wins (mkor_stats inside backend); the copy has
    # no op_name
    assert got == {"forward": pytest.approx(20e-9),
                   "backward": pytest.approx(20e-9),
                   "mkor_precondition": pytest.approx(30e-9),
                   "mkor_smw": pytest.approx(5e-9),
                   "unscoped": pytest.approx(7e-9),
                   "mkor_stats": pytest.approx(3e-9)}
    stages = {"seconds": got, "busy_s": 100e-9}
    assert tracefile.stage_share(stages, ("forward",)) == pytest.approx(20.0)
    assert tracefile.stage_share(stages, ("mkor_stats", "mkor_smw",
                                          "mkor_precondition")) == \
        pytest.approx(38.0)
    assert tracefile.stage_share(stages, ("backend",)) is None


STAGED = DATA / "rwkv6-3b.small.stages.json.gz"


def staged():
    """One chunk of two steps of the cell's program at width 256, traced
    on a TPU v5e, with the compiled chunk's text (record_stage_trace.py);
    inv_freq 2, so that each of its three buckets takes its phase step."""
    if not STAGED.exists():
        pytest.fail(f"recorded trace {STAGED.name} is missing")
    ev = tracefile.read(STAGED)
    return ev, tracefile.op_names(ev.pop("hlo"))


def test_recorded_stages_by_hand():
    ev, names = staged()
    ops = ev["devices"]["0"]
    got = tracefile.stage_seconds(ev, names)
    # loops and calls are left out: the stages hold every other op's time
    inner = [(n, d) for n, _, d in ops
             if tracefile.op_name(n)[1] not in tracefile.CONTAINERS]
    assert sum(got.values()) == pytest.approx(sum(d for _, d in inner) * 1e-9)
    assert len(inner) < len(ops)

    def stages_of(prefix):
        return sorted(tracefile.stage_of(names.get(tracefile.op_name(n)[0],
                                                   ""))
                      for n, _ in inner if tracefile.op_name(n)[0]
                      .startswith(prefix))
    # three buckets: one SMW kernel per side on its phase step, one
    # precondition kernel per step
    assert stages_of("vmap_mkor_smw_") == ["mkor_smw"] * 6
    assert stages_of("vmap_mkor_precond_") == ["mkor_precondition"] * 6
    # the copies and slices XLA inserts carry no op_name
    assert set(stages_of("copy-start") + stages_of("slice-start")) == \
        {tracefile.UNSCOPED}
    assert set(got) == {"forward", "backward", "mkor_stats", "mkor_smw",
                        "mkor_precondition", "backend", "apply",
                        tracefile.UNSCOPED}


@pytest.mark.parametrize("metric", ["forward_device_pct",
                                    "backward_device_pct",
                                    "mkor_device_pct", "backend_device_pct"])
def test_recorded_stage_shares(metric):
    ev, names = staged()
    stages = {"seconds": tracefile.stage_seconds(ev, names),
              "busy_s": tracefile.length(tracefile.busy(ev, "0")) * 1e-9}
    mod = harness.load_module(harness.BENCH / "metrics" / f"{metric}.py")
    value = mod.read({"stages": stages})
    assert value is not None and 0 < value <= 100, value
    assert 100 * sum(stages["seconds"].values()) / stages["busy_s"] <= 100
