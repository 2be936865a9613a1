"""The names of the training step's stages in a profiler trace.

Each stage of a step runs under ``jax.named_scope(<name>)``, so the name
becomes a component of every HLO op's ``op_name`` metadata and of the
device trace's op events; no op changes.  The names:

``forward``
    The forward pass and the loss (``training/loop.py``).  The backward
    pass carries it too, inside ``transpose(jvp(forward))``: an op whose
    path holds ``transpose(`` before ``forward`` is backward.
``mkor_stats``
    MKOR's rank-1 statistic capture, E[a] (``models/layers.py``).
``mkor_smw``
    Stabilize, the Sherman-Morrison or block-Woodbury factor update and
    the health signals on phase steps (``core/mkor.py``).
``mkor_precondition``
    The two-sided precondition and its rescale (``core/mkor.py``).
``backend``
    The first-order optimizer's update (``core/firstorder.py``).
``apply``
    Adding the updates to the parameters, and the step's metrics.
``grad_allreduce``, ``stat_allreduce``, ``owner_gather``
    The data-parallel step's collectives: the gradient (and loss) mean,
    the rank-1 statistic mean, and the gather of owner-sharded inverse
    factors (``training/loop.py``, ``sharding/collectives.py``).

An op belongs to the innermost of these names in its ``op_name``
(:func:`stage_of`); ops that XLA inserts, such as copies, have no
``op_name`` and belong to none.  The host spans of the chunk loop
(``training/loop.run_chunk``) are ``stack_batches``, ``dispatch`` and
``device_get``, inside a ``train`` step span.
"""
from __future__ import annotations

import functools
import re
from typing import Callable

import jax

FORWARD = "forward"
MKOR_STATS = "mkor_stats"
MKOR_SMW = "mkor_smw"
MKOR_PRECONDITION = "mkor_precondition"
BACKEND = "backend"
APPLY = "apply"
GRAD_ALLREDUCE = "grad_allreduce"
STAT_ALLREDUCE = "stat_allreduce"
OWNER_GATHER = "owner_gather"
STAGES = (FORWARD, MKOR_STATS, MKOR_SMW, MKOR_PRECONDITION, BACKEND, APPLY,
          GRAD_ALLREDUCE, STAT_ALLREDUCE, OWNER_GATHER)
BACKWARD = "backward"           # what stage_of calls a transposed forward op

# the Pallas kernels' names (``pl.pallas_call(name=...)``)
SMW_KERNEL = "mkor_smw"
BLOCK_SMW_KERNEL = "mkor_block_smw"
PRECOND_KERNEL = "mkor_precond"
MATMUL_KERNEL = "mkor_matmul"

# host spans of the chunk loop, and the step span around them
STEP_SPAN = "train"
STACK_BATCHES = "stack_batches"
DISPATCH = "dispatch"
DEVICE_GET = "device_get"
HOST_SPANS = (STACK_BATCHES, DISPATCH, DEVICE_GET)

_COMPONENT = re.compile(r"[^/()]+")


def scoped(name: str) -> Callable[[Callable], Callable]:
    """Decorator: run the function under ``jax.named_scope(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


def stage_of(op_name: str) -> str:
    """The stage of an op by its ``op_name``: the innermost name of
    ``STAGES`` among its path's components, ``backward`` for a
    ``forward`` op under ``transpose(``, and ``""`` for none."""
    words = _COMPONENT.findall(op_name)
    for i in range(len(words) - 1, -1, -1):
        if words[i] in STAGES:
            if words[i] == FORWARD and "transpose" in words[:i]:
                return BACKWARD
            return words[i]
    return ""
