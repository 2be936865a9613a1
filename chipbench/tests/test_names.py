"""The benchmark's names for the chunk loop's host spans, the training
step's stages and the Pallas kernels are the program's own
(src/repro/scopes.py), kept as copies: the benchmark imports nothing of
the program."""
import pytest

import harness
import tracefile
from repro import scopes


def test_host_spans_are_the_programs():
    assert tuple(tracefile.HOST_SPANS) == scopes.HOST_SPANS


def test_stage_names_are_the_programs():
    assert tracefile.STAGES == scopes.STAGES
    assert (tracefile.FORWARD, tracefile.BACKWARD) == \
        (scopes.FORWARD, scopes.BACKWARD)


def test_kernel_names_are_the_programs():
    assert (tracefile.SMW_KERNEL, tracefile.BLOCK_SMW_KERNEL,
            tracefile.PRECOND_KERNEL, tracefile.MATMUL_KERNEL) == \
        (scopes.SMW_KERNEL, scopes.BLOCK_SMW_KERNEL, scopes.PRECOND_KERNEL,
         scopes.MATMUL_KERNEL)


@pytest.mark.parametrize("path", [
    "jit(run_chunk)/while/body/forward/mkor_stats/reduce_sum",
    "jit(run_chunk)/while/body/transpose(jvp(forward))/dot_general",
    "jit(run_chunk)/while/body/cond/branch_1_fun/mkor_smw/vmap(mkor_smw)",
    "jit(run_chunk)/while/body/backend/sqrt",
    "jit(run_chunk)/while/body/copy", ""])
def test_stage_of_is_the_programs(path):
    assert tracefile.stage_of(path) == (scopes.stage_of(path)
                                        or tracefile.UNSCOPED)


def test_recorded_op_names_take_the_programs_stages():
    """Every op_name of a compiled chunk of the cell's program
    (data/rwkv6-3b.small.stages.json.gz) gets the stage the program's own
    rule gives it."""
    ev = tracefile.read(harness.BENCH / "tests" / "data" /
                        "rwkv6-3b.small.stages.json.gz")
    paths = set(tracefile.op_names(ev["hlo"]).values())
    assert len(paths) > 100
    for path in paths:
        assert tracefile.stage_of(path) == (scopes.stage_of(path)
                                            or tracefile.UNSCOPED), path
