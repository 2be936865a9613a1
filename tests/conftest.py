import os

# The suite runs on the CPU (Pallas kernels in interpret mode).  Pin it
# before jax starts, so that no test process takes an accelerator that a
# chip run may need; the kernels' TPU compiles (tests/test_tpu_compile.py)
# target a described chip and need none.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# Multi-device paths (sharding/collectives.py, training/loop.py dist step)
# are tested on 8 fake CPU devices via launch/mesh.make_host_mesh(n_data=..)
# — the flag must be set before jax initializes, and the backend is locked
# immediately below so a later import of launch/dryrun.py (which overwrites
# XLA_FLAGS with its 512-device setting for its OWN process) cannot change
# this process's device count mid-suite.
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = \
        "--xla_force_host_platform_device_count=8 " + _flags

import jax
import numpy as np
import pytest

jax.config.update("jax_enable_x64", False)
# Lock the backend now, so device count can no longer change mid-suite.
# On backends where the host flag has no effect (GPU, pre-set XLA_FLAGS)
# this may be < 8 — the dist tests skip themselves rather than failing.
N_DEVICES = jax.device_count()


@pytest.fixture
def rng():
    return np.random.default_rng(0)


# --------------------------------------------------------------------- #
# Session-scoped caches (tier-1 budget): the standard small workloads are
# built once per session instead of once per test.  Everything handed out
# here is treated functionally by the optimizers (params are never mutated
# in place), so sharing is safe.
# --------------------------------------------------------------------- #
@pytest.fixture(scope="session")
def ae_params():
    """The canonical autoencoder params (96 -> 48/12/48) used across the
    MKOR/dist equivalence tests."""
    from repro.core import baseline_net
    return baseline_net.init_autoencoder(jax.random.key(0), 96,
                                         (48, 12, 48))


@pytest.fixture(scope="session")
def ae_manifest(ae_params):
    """Bucket manifest of :func:`ae_params` under the default exclusions."""
    from repro.core.mkor import MKORConfig, manifest_for
    return manifest_for(ae_params, MKORConfig(exclude=()))


@pytest.fixture(scope="session")
def tiny_model_cfg():
    """A 2-layer dense ModelConfig small enough that full train-step
    compiles stay cheap — the shared fixture for model-level plumbing
    tests that do not need a real architecture."""
    from repro.models.config import ModelConfig
    return ModelConfig(name="t", arch_type="dense", n_layers=2, d_model=32,
                       n_heads=2, n_kv_heads=2, d_ff=64, vocab_size=64,
                       dtype="float32", scan_layers=False, remat=False,
                       vocab_pad_multiple=1)


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running integration test")
