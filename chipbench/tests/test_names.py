"""The benchmark's names for the chunk loop's host spans are the
program's own (src/repro/scopes.py): the harness's mirror of the loop
and the launcher's run_chunk write the same spans."""
import tracefile
from repro import scopes


def test_host_spans_are_the_programs():
    assert tuple(tracefile.HOST_SPANS) == scopes.HOST_SPANS
