"""``wkv_device_pct`` reads the device time under the program's ``wkv``
scope, the forward and the backward parts together; the stage readers
keep counting those ops as forward and backward, and a program without
the scope reads nothing."""
import pytest

import harness
import tracefile
from repro import scopes

from test_trace import events, line, staged

WKV_TEXT = '''
HloModule jit_run_chunk
ENTRY %main.9 (p: f32[4]) -> f32[4] {
  %fusion.5 = f32[4]{0} fusion(f32[4]{0} %p.1), kind=kLoop, metadata={op_name="jit(run_chunk)/while/body/forward/checkpoint/wkv/dot_general"}
  %fusion.6 = f32[4]{0} fusion(f32[4]{0} %p.1), kind=kLoop, metadata={op_name="jit(run_chunk)/while/body/transpose(jvp(forward))/checkpoint/wkv/while/body/mul"}
  %fusion.7 = f32[4]{0} fusion(f32[4]{0} %p.1), kind=kLoop, metadata={op_name="jit(run_chunk)/while/body/forward/checkpoint/group_norm/mul"}
  %fusion.8 = f32[4]{0} fusion(f32[4]{0} %p.1), kind=kLoop, metadata={op_name="jit(run_chunk)/while/body/backend/sqrt"}
  ROOT %copy.10 = f32[4]{0} copy(f32[4]{0} %p.1)
}
'''
WKV_OPS = [("fusion.5", 0, 10), ("fusion.6", 10, 30), ("fusion.7", 40, 5),
           ("fusion.8", 45, 10), ("copy.10", 55, 5)]


def reader():
    return harness.load_module(harness.BENCH / "metrics" /
                               "wkv_device_pct.py")


def test_wkv_name_is_the_programs():
    assert reader().WKV == scopes.WKV
    assert scopes.WKV not in scopes.STAGES


def test_wkv_share_by_hand():
    ev = events([(line(WKV_TEXT, n), s, d) for n, s, d in WKV_OPS])
    names = tracefile.op_names(WKV_TEXT)
    ctx = {"events": ev, "op_names": names, "stages": {"busy_s": 60e-9}}
    assert reader().read(ctx) == pytest.approx(100 * 40 / 60)
    # the wkv's ops stay in the stages of the passes they run in
    assert tracefile.stage_seconds(ev, names) == {
        "forward": pytest.approx(15e-9), "backward": pytest.approx(30e-9),
        "backend": pytest.approx(10e-9),
        tracefile.UNSCOPED: pytest.approx(5e-9)}


def test_a_program_without_the_scope_reads_nothing():
    """The recorded chunk of the program before the scope existed."""
    ev, names = staged()
    busy, _ = tracefile.busy_and_window(ev)
    ctx = {"events": ev, "op_names": names, "stages": {"busy_s": busy}}
    assert reader().read(ctx) is None
