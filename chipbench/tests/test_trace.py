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


SMW = ('%vmap_mkor_smw_.9 = bf16[2,256,256]{2,1,0} custom-call(bf16[2,256,256]'
       '{2,1,0} %g.1, f32[2,256,1]{2,1,0} %g.2, f32[2,256,1]{2,1,0} %g.2), '
       'custom_call_target="tpu_custom_call"')
MATMUL = ('%vmap_mkor_matmul_.8 = f32[2,256,64]{2,1,0} custom-call(bf16[2,256,256]'
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
    # kernels by their instructions' names where no text is at hand
    assert tracefile.kernel_seconds(ev, {}, (tracefile.SMW_KERNEL,)) == \
        pytest.approx(10e-9)
    assert tracefile.kernel_seconds(ev, {}, (tracefile.PRECOND_KERNEL,
                                             tracefile.MATMUL_KERNEL)) == \
        pytest.approx(30e-9)
    ops = dict(tracefile.breakdown(ev)["device_ops"])
    assert ops == {"fusion.1 (fusion)": pytest.approx(30e-9),
                   "vmap_mkor_matmul_.8 (tpu_custom_call)":
                       pytest.approx(30e-9),
                   "vmap_mkor_smw_.9 (tpu_custom_call)": pytest.approx(10e-9)}


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
    """The whole-window metrics on the full-width trace; the kernels'
    rooflines on the small one, whose kernels carry their names."""
    if metric.endswith("_roofline_pct"):
        ev, names = staged()
    else:
        ev, names = recorded(), {}
    ctx = dict(ev["ctx"], events=ev, op_names=names, log=lambda m: None)
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
  %vmap_mkor_matmul_.8 = f32[2,256,64]{2,1,0} custom-call(bf16[2,256,256]{2,1,0} %g.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(run_chunk)/while/body/mkor_precondition/vmap(mkor_matmul)"}
  %vmap_mkor_smw_.9 = bf16[2,256,256]{2,1,0} custom-call(bf16[2,256,256]{2,1,0} %g.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(run_chunk)/while/body/cond/branch_1_fun/mkor_smw/vmap(mkor_smw)"}
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


def line(text, instruction):
    """An op event's name: its instruction's HLO text, without metadata."""
    for ln in text.splitlines():
        if f"%{instruction} = " in ln:
            return ln.split(", metadata=")[0].replace("ROOT ", "").strip()
    raise KeyError(instruction)


# A scope that is no stage (a model's attention) forward and backward, and
# a Pallas kernel of its own whose operands include two f32[..., 1], as
# the SMW kernel's do.
ATTN_TEXT = '''
HloModule jit_run_chunk
ENTRY %main.9 (p: f32[4]) -> f32[4] {
  %fusion.5 = f32[4]{0} fusion(f32[4]{0} %p.1), kind=kLoop, metadata={op_name="jit(run_chunk)/while/body/forward/attention/dot_general"}
  %fusion.6 = f32[4]{0} fusion(f32[4]{0} %p.1), kind=kLoop, metadata={op_name="jit(run_chunk)/while/body/transpose(jvp(forward))/attention/dot_general"}
  %vmap_flash_fwd_.7 = f32[2,256,64]{2,1,0} custom-call(bf16[2,256,256]{2,1,0} %g.1, f32[2,256,1]{2,1,0} %g.2, f32[2,256,1]{2,1,0} %g.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(run_chunk)/while/body/forward/attention/vmap(flash_fwd)/pallas_call"}
  %fusion.8 = f32[4]{0} fusion(f32[4]{0} %p.1), kind=kLoop, metadata={op_name="jit(run_chunk)/while/body/forward/mlp/mul"}
  %vmap_mkor_smw_.9 = bf16[2,256,256]{2,1,0} custom-call(bf16[2,256,256]{2,1,0} %g.1, f32[2,256,1]{2,1,0} %g.2, f32[2,256,1]{2,1,0} %g.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(run_chunk)/while/body/cond/branch_1_fun/mkor_smw/vmap(mkor_smw)/pallas_call"}
  ROOT %copy.10 = f32[4]{0} copy(f32[4]{0} %p.1)
}
'''
ATTN_OPS = [("fusion.5", 0, 10), ("fusion.6", 10, 20),
            ("vmap_flash_fwd_.7", 30, 40), ("fusion.8", 70, 7),
            ("vmap_mkor_smw_.9", 77, 5), ("copy.10", 82, 3)]


def attention_events():
    return events([(line(ATTN_TEXT, n), s, d) for n, s, d in ATTN_OPS])


def test_scope_outside_the_stages_by_hand():
    ev, names = attention_events(), tracefile.op_names(ATTN_TEXT)
    got = tracefile.scope_seconds(ev, names, ("attention",))
    assert got == {"attention": pytest.approx(50e-9),
                   tracefile.backward_part("attention"): pytest.approx(20e-9),
                   tracefile.UNSCOPED: pytest.approx(15e-9)}
    # the stages do not see the new scope: its ops keep the forward pass's
    # stage and its backward's
    assert tracefile.stage_seconds(ev, names) == {
        "forward": pytest.approx(57e-9), "backward": pytest.approx(20e-9),
        "mkor_smw": pytest.approx(5e-9),
        tracefile.UNSCOPED: pytest.approx(3e-9)}
    ctx = {"events": ev, "op_names": names, "stages": {"busy_s": 85e-9}}
    assert tracefile.scope_share(ctx, ("attention",)) == \
        pytest.approx(100 * 70 / 85)
    assert tracefile.scope_share(ctx, ("router",)) is None


def test_a_kernel_of_its_own_is_in_neither_mkor_reader():
    ev, names = attention_events(), tracefile.op_names(ATTN_TEXT)
    assert tracefile.kernel_seconds(ev, names, ("flash_fwd",)) == \
        pytest.approx(40e-9)
    peaks = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}
    ctx = {"events": ev, "op_names": names, "peaks": peaks,
           "work": {"smw": (0.0, 1.0), "precond": (0.0, 1.0)},
           "log": lambda m: None}
    smw = harness.load_module(harness.BENCH / "metrics" /
                              "smw_roofline_pct.py")
    precond = harness.load_module(harness.BENCH / "metrics" /
                                  "precond_roofline_pct.py")
    # the SMW reader times the mkor_smw kernel alone: 1 ns least, 5 ns run
    assert smw.read(ctx) == pytest.approx(20.0)
    assert precond.read(ctx) is None


@pytest.mark.parametrize("instruction,path,kernel", [
    ("vmap_mkor_smw_.11", "jit(run_chunk)/while/body/closed_call/cond/"
     "branch_1_fun/mkor_smw/vmap(mkor_smw)/pallas_call", "mkor_smw"),
    ("vmap_mkor_matmul_.8",
     "jit(run_chunk)/while/body/mkor_precondition/vmap(mkor_matmul)",
     "mkor_matmul"),
    ("x.1", "jit(f)/vmap(vmap(flash_fwd))/pallas_call", "flash_fwd"),
    ("vmap_mkor_matmul_.76", "", "mkor_matmul"),
    ("vmap_vmap_mkor_precond__.3", "", "mkor_precond"),
    ("mkor_block_smw.2", "", "mkor_block_smw"),
    # a kernel given no name, in the mkor_smw scope: no kernel's name
    ("vmap__.88", "jit(run_chunk)/while/body/mkor_smw/vmap()/pallas_call",
     ""),
    ("vmap__.88", "", "")])
def test_kernel_of(instruction, path, kernel):
    assert tracefile.kernel_of(instruction, path) == kernel


def test_reader_added_as_a_file(tmp_path, monkeypatch, data_cell):
    """A configuration's readers come as new files of metrics/ alone: a
    scope that is no stage, its own kernel's time, and the cell's sizes
    and traffic, all from the context the harness builds."""
    metrics = tmp_path / "metrics"
    metrics.mkdir()
    (metrics / "attention_device_pct.py").write_text(
        "import tracefile\n\n\ndef read(ctx):\n"
        "    return tracefile.scope_share(ctx, ('attention',))\n")
    (metrics / "router_device_pct.py").write_text(
        "import tracefile\n\n\ndef read(ctx):\n"
        "    return tracefile.scope_share(ctx, ('router',))\n")
    (metrics / "flash_fwd_roofline_pct.py").write_text(
        "import counts\nimport tracefile\n\n\ndef read(ctx):\n"
        "    sz, load = ctx['sz'], ctx['load']\n"
        "    t = tracefile.kernel_seconds(ctx['events'], ctx['op_names'],\n"
        "                                 ('flash_fwd',))\n"
        "    rows = load['batch_per_chip'] * ctx['cell']['chips']\n"
        "    flops = 4.0 * rows * load['seq_len'] ** 2 * sz['n_heads'] \\\n"
        "        * sz['head_dim']\n"
        "    least, _ = counts.least_time(flops, 0.0, ctx['peaks'])\n"
        "    return 100.0 * least / t\n")
    cell = data_cell("qwen2-moe-a2.7b.small")
    run = harness.Run(cell, 3)
    peaks = {"bf16_flops": 1e21, "hbm_bytes_per_s": 1e12}
    ctx = harness.reader_context(run, attention_events(),
                                 tracefile.op_names(ATTN_TEXT), peaks=peaks)
    monkeypatch.setattr(harness, "BENCH", tmp_path)
    got = harness.read_metrics(
        [{"name": n, "unit": "%"} for n in ("attention_device_pct",
                                             "router_device_pct",
                                             "flash_fwd_roofline_pct")],
        ctx)
    sz = harness.sizes(cell["model"])
    flops = 4.0 * 2 * 16 ** 2 * sz["n_heads"] * sz["head_dim"]
    assert got == {
        "attention_device_pct": {"value": pytest.approx(100 * 70 / 85),
                                 "unit": "%"},
        "flash_fwd_roofline_pct": {
            "value": pytest.approx(100 * flops / 1e21 / 40e-9), "unit": "%"}}
    assert ctx["cell"] == {"name": cell["name"], "chips": 1}


STAGED = DATA / "rwkv6-3b.small.stages.json.gz"


def staged():
    """One chunk of two steps of the cell's program at width 256, traced
    on a TPU v5e, with the compiled chunk's text and the kernel readers'
    context (record_stage_trace.py); inv_freq 2, so that each of its
    three buckets takes its phase step."""
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


@pytest.mark.parametrize("recording", ["full-width", "small"])
def test_scope_seconds_over_the_stages_is_stage_seconds(recording):
    ev, names = staged() if recording == "small" else (recorded(), {})
    scopes = tracefile.scope_seconds(ev, names, tracefile.STAGES)
    stages = tracefile.stage_seconds(ev, names)
    back = tracefile.backward_part(tracefile.FORWARD)
    assert {tracefile.BACKWARD if k == back else k: v
            for k, v in scopes.items()} == stages


def test_recorded_kernels_by_name():
    """Every Pallas call of the small trace is one of the program's named
    kernels, found alike by op_name and by instruction name."""
    ev, names = staged()
    calls = [tracefile.op_name(n)[0] for n, _, _ in ev["devices"]["0"]
             if tracefile.PALLAS in n]
    assert sorted({tracefile.kernel_of(c, names[c]) for c in calls}) == \
        [tracefile.PRECOND_KERNEL, tracefile.SMW_KERNEL]
    for kernels in ((tracefile.SMW_KERNEL,), (tracefile.PRECOND_KERNEL,
                                              tracefile.MATMUL_KERNEL)):
        by_name = tracefile.kernel_seconds(ev, names, kernels)
        assert by_name > 0
        assert tracefile.kernel_seconds(ev, {}, kernels) == by_name
