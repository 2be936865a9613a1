"""Launcher tests: the train.py / serve.py CLIs at reduced scale, train.py's
main and platform gates in-process, the compile-cache placement, and
chip_smoke.py's refusal to report a result off the chip."""
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

# children run on the CPU too: a chip belongs to one process at a time
ENV = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")


def run_cli(args, timeout=420):
    return subprocess.run([sys.executable, "-m", *args], env=ENV,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.slow
def test_train_cli_reduced(tmp_path):
    r = run_cli(["repro.launch.train", "--arch", "minicpm-2b", "--reduced",
                 "--steps", "12", "--global-batch", "4", "--seq-len", "32",
                 "--log-every", "4",
                 "--ckpt-dir", str(tmp_path / "ck"),
                 "--ckpt-every", "8",
                 "--log-json", str(tmp_path / "log.json")])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "done: final loss" in r.stdout
    assert (tmp_path / "log.json").exists()
    assert any(d.startswith("step_") for d in os.listdir(tmp_path / "ck"))


@pytest.mark.slow
def test_train_cli_resumes_from_checkpoint(tmp_path):
    ck = str(tmp_path / "ck")
    r1 = run_cli(["repro.launch.train", "--arch", "rwkv6-3b", "--reduced",
                  "--steps", "6", "--global-batch", "2", "--seq-len", "32",
                  "--ckpt-dir", ck])
    assert r1.returncode == 0, r1.stderr[-2000:]
    r2 = run_cli(["repro.launch.train", "--arch", "rwkv6-3b", "--reduced",
                  "--steps", "8", "--global-batch", "2", "--seq-len", "32",
                  "--ckpt-dir", ck])
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "restored checkpoint" in r2.stdout


@pytest.mark.slow
def test_serve_cli_reduced():
    r = run_cli(["repro.launch.serve", "--arch", "gemma2-9b", "--reduced",
                 "--batch", "2", "--prompt-len", "16", "--n-tokens", "8"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "decode" in r.stdout


@pytest.mark.slow
def test_train_cli_mkor_pallas_interpret(tmp_path):
    """MKOR with the Pallas kernel path (interpret mode) trains."""
    r = run_cli(["repro.launch.train", "--arch", "bert-large", "--reduced",
                 "--steps", "4", "--global-batch", "2", "--seq-len", "16",
                 "--use-pallas", "--inv-freq", "2"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "done: final loss" in r.stdout


# --------------------------------------------------------------------- #
# launch/train.py main() in-process, and its platform gates
# --------------------------------------------------------------------- #
TINY = ["--arch", "bert-large", "--reduced", "--optimizer", "mkor",
        "--steps", "4", "--chunk", "2", "--global-batch", "4",
        "--seq-len", "16", "--inv-freq", "2", "--log-every", "1"]


@pytest.mark.parametrize("extra", [[], ["--dist", "--dist-devices", "4"]],
                         ids=["single", "dist"])
def test_train_main_returns_history(monkeypatch, tmp_path, extra):
    """main(argv) runs in the caller's process and returns one metrics
    dict per logged step (chip_smoke.py drives it this way).  The chunk
    runner compiles once: the state it is first handed already has the
    placement it returns (for --dist, replicated on the mesh)."""
    import jax
    from repro.launch import compile_cache, train
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    compiled = []

    def on_compile(event, duration_secs, fun_name="", **kwargs):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(fun_name)

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        history = train.main(TINY + extra)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
    assert [h["step"] for h in history] == [0, 1, 2, 3]
    assert all(np.isfinite(h["loss"]) for h in history)
    assert compiled.count("jit(run_chunk)") == 1, compiled


def test_train_main_profiles_chunks(monkeypatch, tmp_path):
    """--profile-dir traces the chunks after the first two: the written
    .xplane.pb holds the chunk loop's host spans.  The loop is
    training/loop.run_chunk, the one train_epoch runs: over the same
    batches both log the same losses."""
    import glob

    import jax
    from repro import scopes
    from repro.configs import registry
    from repro.data import pipeline
    from repro.launch import compile_cache, train
    from repro.models import model as model_lib
    from repro.training import loop
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "cache"))
    argv = TINY[:TINY.index("--steps")] + ["--steps", "6"] \
        + TINY[TINY.index("--steps") + 2:]
    prof = tmp_path / "profile"
    history = train.main(argv + ["--profile-dir", str(prof),
                                 "--profile-chunks", "1"])
    files = glob.glob(str(prof / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    assert len(files) == 1, files
    data = jax.profiler.ProfileData.from_file(files[0])
    spans = [e.name for plane in data.planes for line in plane.lines
             for e in line.events if e.name in scopes.HOST_SPANS]
    assert sorted(spans) == sorted(scopes.HOST_SPANS), spans

    cfg = registry.get_config("bert-large").reduced()
    opt, _ = train.build_optimizer(
        "mkor", train.build_schedule("cosine", 1e-3, 6), inv_freq=2)
    params = model_lib.init_params(jax.random.PRNGKey(0), cfg)
    ds = pipeline.make_dataset(cfg, global_batch=4, seq_len=16, seed=0)
    _, _, epoch = loop.train_epoch(
        loop.make_train_step(cfg, opt), params, opt.init(params),
        [pipeline.make_batch(ds, i) for i in range(6)], chunk=2)
    assert [h["loss"] for h in history] == [h["loss"] for h in epoch]


def test_use_pallas_interprets_only_on_a_requested_cpu(monkeypatch):
    import jax
    from repro.launch import train
    _, mcfg = train.build_optimizer("mkor", 1e-3, use_pallas=True,
                                    platform="tpu")
    assert mcfg.interpret is False
    _, mcfg = train.build_optimizer("mkor", 1e-3, use_pallas=True,
                                    platform="cpu")
    assert mcfg.interpret is True
    with pytest.raises(SystemExit, match="the backend is gpu"):
        train.build_optimizer("mkor", 1e-3, use_pallas=True, platform="gpu")
    # a CPU nobody asked for is where JAX lands when the TPU fails to start
    was = jax.config.jax_platforms
    try:
        jax.config.update("jax_platforms", None)
        with pytest.raises(SystemExit, match="JAX_PLATFORMS=cpu"):
            train.build_optimizer("mkor", 1e-3, use_pallas=True,
                                  platform="cpu")
    finally:
        jax.config.update("jax_platforms", was)


def test_dist_devices_default_and_bound():
    import jax
    from repro.launch import train
    assert jax.default_backend() == "cpu"
    assert train.resolve_dist_devices(None) == train.HOST_DIST_DEVICES
    assert train.resolve_dist_devices(4) == 4
    with pytest.raises(SystemExit, match="only 8 cpu device"):
        train.resolve_dist_devices(16)


# --------------------------------------------------------------------- #
# Persistent compilation cache placement (launch/compile_cache.py)
# --------------------------------------------------------------------- #
def test_compile_cache_env_dir_stands(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, JAX's own setting stands and
    the cache entries land there."""
    cache = tmp_path / "cache"
    code = ("import jax\n"
            "from repro.launch import compile_cache\n"
            "print(compile_cache.enable())\n"
            "print(jax.config.jax_compilation_cache_dir)\n"
            "jax.config.update("
            "'jax_persistent_cache_min_compile_time_secs', 0)\n"
            "jax.jit(lambda x: x * 2 + 1)(1.0).block_until_ready()\n")
    r = subprocess.run([sys.executable, "-c", code],
                       env=dict(ENV, JAX_COMPILATION_CACHE_DIR=str(cache)),
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split() == [str(cache), str(cache)]
    assert os.listdir(cache)


def test_compile_cache_default_is_the_checkout(monkeypatch):
    """Unset, the cache goes to the fixed, gitignored <checkout>/.jax_cache
    — never a name that changes from run to run."""
    import jax
    from repro.launch import compile_cache
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable()
        assert path == os.path.join(checkout, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    with open(os.path.join(checkout, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


# --------------------------------------------------------------------- #
# chip_smoke.py refuses to report a result off the chip
# --------------------------------------------------------------------- #
def _smoke(script, cwd):
    return subprocess.run([sys.executable, script], cwd=cwd,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_refuses_the_cpu():
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = _smoke(os.path.join(checkout, "chip_smoke.py"), checkout)
    assert r.returncode != 0
    assert "JAX found no TPU" in r.stderr
    assert '"ok": true' not in r.stdout


def test_chip_smoke_refuses_outside_a_checkout(tmp_path):
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    shutil.copy(os.path.join(checkout, "chip_smoke.py"), tmp_path)
    r = _smoke(str(tmp_path / "chip_smoke.py"), str(tmp_path))
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
