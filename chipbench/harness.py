"""One run of one benchmark cell: set-up, measured window, check.

A cell is an entry of ``workloads`` in BENCHMARK.json; everything that
belongs to it is found by name: ``configs/<config>.json`` (the model as
run), ``traffic/<traffic>.json`` (batch, sequence, optimizer, data),
``limits/<cell>.json`` (the limits of the check), ``metrics/<metric>.py``
(one reader per per-layer metric) and ``reference/<name>.py`` (the plain
reference the configuration names).

The run builds what ``repro.launch.train`` builds (the registry model,
``build_optimizer``, ``make_train_step`` or ``make_dist_train_step``,
``make_chunk_runner``) and drives it as the launcher's chunk loop does:
``stack_batches``, the runner call, ``device_get`` of the chunk's metrics.
Its first chunk is also the chunk the check compares with the reference.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import shutil
import time
from pathlib import Path
from typing import Callable, Dict, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_trace"
GIB = 2.0 ** 30


def load_json(path: Path) -> Dict:
    return json.loads(path.read_text())


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_spec(name: str) -> Dict:
    """The cell's manifest entry with its configuration, traffic, limits
    and the metrics it reports."""
    manifest = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = dict(cells[name])
    cell["model"] = load_json(BENCH / "configs" / f"{cell['config']}.json")
    cell["load"] = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    cell["limits"] = load_json(BENCH / "limits" / f"{name}.json")

    def mine(m):
        return name in m.get("workloads", [name])

    cell["end_to_end"] = [m for m in manifest["end_to_end"] if mine(m)]
    cell["per_layer"] = [m for m in manifest["per_layer"] if mine(m)]
    return cell


def sizes(model: Dict) -> Dict:
    """The configuration file's published keys as the reference and the
    counts read them; ``structure`` names the program's model fields the
    file expects."""
    st = model["structure"]
    d = model["hidden_size"]
    head_dim = model.get("head_size") or d // model["num_attention_heads"]
    eps = next(model[k] for k in ("rms_norm_eps", "layer_norm_eps",
                                  "layer_norm_epsilon") if k in model)
    return {
        "d_model": d, "n_heads": d // head_dim,
        "n_kv_heads": model.get("num_key_value_heads", d // head_dim),
        "head_dim": head_dim, "d_ff": model["intermediate_size"],
        "vocab": model["vocab_size"], "n_layers": model["num_hidden_layers"],
        "norm": st["norm"], "eps": eps, "gated": st["gated_mlp"],
        "causal": st["causal"], "tied": st["tie_embeddings"],
        "rope_theta": model.get("rope_theta", model.get(
            "assumed", {}).get("rope_theta", 10000.0)),
    }


def model_config(model: Dict, sz: Dict):
    """The registry's ModelConfig with the file's numbers; refuses a file
    whose ``structure`` the registry entry does not have."""
    from repro.configs import registry
    base = registry.get_config(model["arch"])
    cfg = dataclasses.replace(
        base, n_layers=sz["n_layers"], d_model=sz["d_model"],
        n_heads=sz["n_heads"], n_kv_heads=sz["n_kv_heads"], head_dim=0,
        d_ff=sz["d_ff"], vocab_size=sz["vocab"], norm_eps=sz["eps"],
        rope_theta=sz["rope_theta"], dtype=model["torch_dtype"])
    want = model["structure"]
    have = {k: getattr(cfg, k) for k in want}
    if have != want or len(cfg.pattern) != 1:
        raise SystemExit(f"{model['arch']}: the program's model is {have}, "
                         f"the configuration file states {want}")
    return cfg


class Run:
    """The objects of one cell's run, built once and driven by the
    set-up, the window and the check."""

    def __init__(self, cell: Dict, seed: int, *,
                 breakage: Optional[Callable] = None):
        import jax
        self.jax = jax
        self.cell = cell
        self.load, self.opt_spec = cell["load"], cell["load"]["optimizer"]
        self.sz = sizes(cell["model"])
        self.cfg = model_config(cell["model"], self.sz)
        self.chips = cell["chips"]
        self.chunk = self.load["chunk"]
        self.batch = self.load["batch_per_chip"] * self.chips
        self.seq = self.load["seq_len"]
        self.breakage = breakage
        self.reseed(seed)
        self.mesh = self.dist = None
        if self.chips > 1:
            from repro.launch import mesh as mesh_lib
            from repro.sharding import collectives
            self.mesh = mesh_lib.make_host_mesh(n_data=self.chips)
            self.dist = collectives.dist_axes(self.mesh,
                                              mesh_lib.mesh_axes(self.mesh))

    def reseed(self, seed: int):
        """Seeds of the data and of the weights, both from ``seed``."""
        self.seed = seed
        ss = np.random.SeedSequence(seed)
        self.data_seed, self.weight_seed = (int(x) for x in
                                            ss.generate_state(2))

    # ---------------------------------------------------------------- #
    def optimizer(self, name: Optional[str] = None):
        from repro.launch.train import build_optimizer
        o = self.opt_spec
        return build_optimizer(
            name or o["name"], o["lr"], inv_freq=o["inv_freq"],
            rank=o["rank"], staleness=o["staleness"],
            use_pallas=o["use_pallas"],
            platform=self.jax.default_backend(), dist=self.dist,
            quant=o["quant"])

    def runner(self, opt):
        from repro.training import loop
        if self.mesh is not None:
            step = loop.make_dist_train_step(self.cfg, opt, self.mesh)
        else:
            step = loop.make_train_step(self.cfg, opt)
        if self.breakage is not None:
            step = self.breakage(step)
        return loop.make_chunk_runner(step)

    def replicated(self):
        from jax.sharding import NamedSharding, PartitionSpec, \
            SingleDeviceSharding
        if self.mesh is None:
            return SingleDeviceSharding(self.jax.devices()[0])
        return NamedSharding(self.mesh, PartitionSpec())

    def weights(self):
        """Seeded weights in the program's tree layout, made on the device
        in one jitted call, each leaf by the configuration's ``init`` rule
        for the longest suffix of its path that has one ("mixer/o/w"
        before "w"; 0 where none has)."""
        jax, jnp = self.jax, self.jax.numpy
        from repro.models import model as model_lib
        shapes = jax.eval_shape(
            lambda k: model_lib.init_params(k, self.cfg),
            jax.random.PRNGKey(0))
        flat, tree = jax.tree_util.tree_flatten_with_path(shapes)
        rules = self.cell["model"]["init"]

        def rule(path):
            names = [str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path]
            for i in range(len(names)):
                suffix = "/".join(names[i:])
                if suffix in rules:
                    return rules[suffix]
            return ("const", 0.0)

        def leaf(key, path, s):
            kind, a = rule(path)
            if kind == "normal_fan_in":
                return jax.random.normal(key, s.shape) * (
                    a / math.sqrt(s.shape[-2]))
            if kind == "normal":
                return jax.random.normal(key, s.shape) * a
            if kind == "uniform":
                return jax.random.uniform(key, s.shape, minval=-a, maxval=a)
            return jnp.full(s.shape, a, jnp.float32)

        def make(key):
            return jax.tree_util.tree_unflatten(tree, [
                leaf(jax.random.fold_in(key, i), path, s).astype(s.dtype)
                for i, (path, s) in enumerate(flat)])

        key = jax.random.PRNGKey(self.weight_seed % 2 ** 31)
        return jax.jit(make, out_shardings=self.replicated())(key)

    def pool(self):
        from data import batch_pool
        n = self.chunk * self.load["pool_chunks"]
        return batch_pool(self.data_seed, n, self.batch, self.seq,
                          self.sz["vocab"], self.load["data"])


# -------------------------------------------------------------------- #
# The program's side of the check
# -------------------------------------------------------------------- #
def program_numbers(run: Run, mcfg, params0_host, params1, opt_state,
                    metrics: Dict) -> Dict:
    """The numbers the check compares, read from the program's state and
    metrics after its first chunk."""
    jax, jnp = run.jax, run.jax.numpy
    from repro.core.mkor import manifest_for
    b2 = run.opt_spec["b2"]

    def offdiag(f):
        d = f.shape[-1]
        f = f.astype(jnp.float32) * (1 - jnp.eye(d, dtype=jnp.float32))
        return jnp.sqrt(jnp.sum(f * f, axis=(-2, -1)))

    def device_side(params, st):
        v = st["backend"]["v"]
        grad = {jax.tree_util.keystr(k): jnp.sqrt(jnp.sum(x))
                for k, x in jax.tree_util.tree_leaves_with_path(v)}
        factor = {}
        for b in manifest_for(params, mcfg):
            bank = st["factor_banks"][b.bucket_id]
            for side, key in (("l", "l_inv"), ("r", "r_inv")):
                f = bank[key].astype(jnp.float32)
                if f"{side}_scale" in bank:
                    f = f * bank[f"{side}_scale"][..., None, None]
                norms = offdiag(f)
                for i, ps in enumerate(b.path_strs):
                    factor[f"{ps}/{side}"] = norms[i]
        return grad, factor

    grad, factor = jax.device_get(jax.jit(device_side)(params1, opt_state))
    p1 = jax.device_get(params1)
    update = {}
    for (k, a), b in zip(jax.tree_util.tree_leaves_with_path(p1),
                         jax.tree.leaves(params0_host)):
        update[jax.tree_util.keystr(k)] = float(np.linalg.norm(
            np.asarray(a, np.float32).ravel()
            - np.asarray(b, np.float32).ravel()))
    return {"loss": [float(x) for x in np.ravel(metrics["loss"])],
            "grad": {k: float(v) / math.sqrt(1 - b2) for k, v in grad.items()},
            "update": update,
            "factor": {k: np.asarray(v, np.float64) for k, v in factor.items()}}


def reference_numbers(run: Run, params0_host, batches,
                      control: Optional[str] = None) -> Dict:
    """The plain reference the configuration names, trained by the plain
    MKOR-over-LAMB reference over the same batches from the same weights.
    ``control`` (a key of ``CONTROLS``) trains it in that lower precision
    instead: the check's control."""
    from reference import layers, mkor_lamb
    with layers.lower(CONTROLS[control] if control else None):
        return mkor_lamb.run(reference_model(run.cell), params0_host,
                             batches, run.sz, run.opt_spec)


# The control: the reference put in the program's place, trained one step
# below the bfloat16 the configuration states (fp8 training: weights and
# forward operands in e4m3, backward gradients in e5m2: (exponent bits,
# mantissa bits) each).
CONTROLS = {"fp8": ((4, 3), (5, 2))}


def reference_model(cell: Dict):
    import importlib
    return importlib.import_module(f"reference.{cell['model']['reference']}")


# -------------------------------------------------------------------- #
# Window
# -------------------------------------------------------------------- #
class Driver:
    """The launcher's chunk loop over the cell's batch pool."""

    def __init__(self, run: Run, runner, params, opt_state, pool):
        self.run, self.runner = run, runner
        self.params, self.opt_state, self.pool = params, opt_state, pool
        self.next = 0
        self.steps = self.failed = 0

    def chunk(self):
        from repro.training import loop
        jax = self.run.jax
        c = self.run.chunk
        n_chunks = len(self.pool) // c
        j = self.next % n_chunks
        self.next += 1
        with jax.profiler.TraceAnnotation("stack_batches"):
            stacked = loop.stack_batches(self.pool[j * c:(j + 1) * c])
        with jax.profiler.TraceAnnotation("dispatch"):
            self.params, self.opt_state, metrics = self.runner(
                self.params, self.opt_state, stacked)
        with jax.profiler.TraceAnnotation("device_get"):
            metrics = jax.device_get(metrics)
        loss = np.asarray(metrics["loss"])
        self.steps += loss.size
        self.failed += int(np.sum(~np.isfinite(loss)))
        return metrics

    def window(self, seconds: float):
        """Whole chunks until ``seconds`` have passed: (chunks, s)."""
        t0 = time.perf_counter()
        n = 0
        while True:
            self.chunk()
            n += 1
            if time.perf_counter() - t0 >= seconds:
                return n, time.perf_counter() - t0

    def timed(self, n: int) -> float:
        t0 = time.perf_counter()
        for _ in range(n):
            self.chunk()
        return (time.perf_counter() - t0) / n


class CompileCounter:
    """Counts compilations and compile-cache reads JAX reports."""

    def __init__(self, jax):
        self.n = 0

        def seen(event, *args, **kw):
            if event.startswith("/jax/core/compile/backend_compile") or \
                    event == "/jax/compilation_cache/cache_hits":
                self.n += 1

        jax.monitoring.register_event_listener(seen)
        jax.monitoring.register_event_duration_secs_listener(seen)


def state_bytes(jax, opt, params) -> int:
    shapes = jax.eval_shape(opt.init, params)
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))


def peak_bytes(jax) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


def device_info(jax, chips: int, peak: int) -> Dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": chips, "memory_peak_bytes": peak}


# -------------------------------------------------------------------- #
def start(run: Run, opt, mcfg, runner, t0: float, log=print):
    """Set-up from the seed: weights, optimizer state, batch pool, and the
    first two chunks (the first compiles, or reads the compile cache, and
    is the chunk the check follows).  Returns (driver, the program's
    numbers, the initial weights on the host, setup seconds)."""
    jax = run.jax
    params = run.weights()
    opt_state = jax.jit(opt.init, out_shardings=run.replicated())(params)
    pool = run.pool()
    t_check = time.perf_counter()
    params0 = jax.device_get(params)
    t_check = time.perf_counter() - t_check
    drv = Driver(run, runner, params, opt_state, pool)
    t_first = time.perf_counter()
    first = drv.chunk()
    t_first = time.perf_counter() - t_first
    t_read = time.perf_counter()
    prog = program_numbers(run, mcfg, params0, drv.params, drv.opt_state,
                           first)
    t_check += time.perf_counter() - t_read
    drv.chunk()
    setup_s = time.perf_counter() - t0 - t_check
    log(f"setup_s={setup_s:.3f} first_chunk_s={t_first:.3f} "
        f"check_readings_s={t_check:.3f}")
    return drv, prog, params0, setup_s


def enable_cache(jax):
    from repro.launch import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def execute(cell: Dict, seed: int, seconds: float, trace: bool, t0: float,
            *, breakage: Optional[Callable] = None,
            control: Optional[str] = None, log=print) -> Dict:
    """One run; returns the result object (without printing it).  With
    ``control`` (a key of ``CONTROLS``) the check compares the reference
    in that precision, in the program's place, with the reference."""
    run = Run(cell, seed, breakage=breakage)
    jax = run.jax
    enable_cache(jax)
    counter = CompileCounter(jax)
    d0 = jax.devices()[0]
    log(f"device platform={d0.platform} kind={d0.device_kind} "
        f"count={jax.device_count()} cell={cell['name']} seed={seed}")
    opt, mcfg = run.optimizer()
    runner = run.runner(opt)
    drv, prog, params0, setup_s = start(run, opt, mcfg, runner, t0, log)

    tokens_per_chunk = run.chunk * run.batch * run.seq
    metrics, extra = {}, {}
    drv.steps = drv.failed = 0
    if not trace:
        compiles0 = counter.n
        n, elapsed = drv.window(seconds)
        peak = peak_bytes(jax)
        metrics["tokens_per_s"] = {"value": n * tokens_per_chunk / elapsed,
                                   "unit": "tokens/s"}
        metrics["peak_hbm_gib"] = {"value": peak / GIB, "unit": "GiB"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        log(f"window chunks={n} seconds={elapsed:.3f}")
        compiles = counter.n - compiles0
    else:
        metrics, extra, peak, compiles = traced(
            run, drv, opt, mcfg, tokens_per_chunk, counter, log)
    attempted, failed = drv.steps, drv.failed
    drv.params = drv.opt_state = None
    del drv, runner

    if control:
        prog = reference_numbers(run, params0, run.pool()[:run.chunk],
                                 control=control)
    t_ref = time.perf_counter()
    ref = reference_numbers(run, params0, run.pool()[:run.chunk])
    t_ref = time.perf_counter() - t_ref
    import check
    correct, rows = check.verdict(check.gaps(prog, ref), cell["limits"])
    log(f"reference_s={t_ref:.3f} compiles_in_window={compiles}")
    checks = {k: {"value": r["value"], "limit": r["limit"]}
              for k, r in rows.items()}
    checks["nonfinite_steps"] = {"value": failed, "limit": 0}
    checks["window_compiles"] = {"value": compiles, "limit": 0}
    device = device_info(jax, run.chips, peak)
    device.update(extra.pop("device", {}))
    return {"correct": bool(correct and failed == 0 and compiles == 0),
            "attempted": attempted, "failed": failed, "metrics": metrics,
            "device": device, **extra, "check": checks}


def kernel_work(run: Run, mcfg, params, count0: int, n_steps: int):
    """FLOPs and bytes of the SMW updates and of the precondition that
    device 0 runs in steps [count0, count0 + n_steps): every layer slice
    is preconditioned each step (on every chip), and each bucket's
    factors are updated on its phase steps (owner-sharded over the chips
    of the dist step)."""
    import counts
    from repro.core import stats as statlib
    from repro.core.mkor import manifest_for
    manifest = manifest_for(params, mcfg)
    phases = statlib.bucket_phases(manifest, mcfg.inv_freq, mcfg.stagger)
    f_item = statlib.factor_itemsize(mcfg.factor_dtype, mcfg.factor_quant)
    g_item = np.dtype(run.cfg.dtype).itemsize
    smw, pre = [0.0, 0.0], [0.0, 0.0]
    for t in range(count0, count0 + n_steps):
        for b in manifest:
            n = b.n_slots * int(np.prod(b.stack, dtype=np.int64))
            f, by = counts.precond_cost(b.d_in, b.d_out, n, f_item, g_item)
            pre[0] += f
            pre[1] += by
            if t % mcfg.inv_freq == phases[b.bucket_id]:
                mine = -(-n // run.chips)
                for d in (b.d_in, b.d_out):
                    f, by = counts.smw_cost(d, mine, f_item)
                    smw[0] += f
                    smw[1] += by
    return {"smw": tuple(smw), "precond": tuple(pre)}


def traced(run: Run, drv: Driver, opt, mcfg, tokens_per_chunk, counter,
           log):
    """The --trace 1 run: a traced window, then host-clock chunk times of
    MKOR and of a LAMB-only twin.  Returns (metrics, extra, peak,
    compilations in the traced and timed MKOR chunks)."""
    import counts
    import tracefile
    from repro.training import loop
    jax = run.jax
    stacked = loop.stack_batches(drv.pool[:run.chunk])
    ma = drv.runner.lower(drv.params, drv.opt_state, stacked) \
        .compile().memory_analysis()
    if ma is not None:
        log(f"compiled chunk: arguments {ma.argument_size_in_bytes / GIB:.3f}"
            f" GiB, temporaries {ma.temp_size_in_bytes / GIB:.3f} GiB, "
            f"outputs {ma.output_size_in_bytes / GIB:.3f} GiB (aliased "
            f"{ma.alias_size_in_bytes / GIB:.3f} GiB)")
    n_trace = run.load["trace_chunks"]
    work = kernel_work(run, mcfg, drv.params, drv.next * run.chunk,
                       n_trace * run.chunk)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    compiles0 = counter.n
    jax.profiler.start_trace(str(TRACE_DIR))
    t0 = time.perf_counter()
    for _ in range(n_trace):
        drv.chunk()
    window_host_s = time.perf_counter() - t0
    jax.profiler.stop_trace()
    events = tracefile.load(TRACE_DIR)
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    peak = peak_bytes(jax)

    n_twin = run.load["twin_chunks"]
    mkor_chunk_s = drv.timed(n_twin)
    compiles = counter.n - compiles0
    lamb, _ = run.optimizer(name="lamb")
    mkor_state = state_bytes(jax, opt, drv.params)
    lamb_state = state_bytes(jax, lamb, drv.params)
    steps, failed = drv.steps, drv.failed
    drv.params = drv.opt_state = None
    params = run.weights()
    twin = Driver(run, run.runner(lamb), params,
                  jax.jit(lamb.init, out_shardings=run.replicated())(params),
                  drv.pool)
    del params
    twin.chunk()
    twin.chunk()
    lamb_chunk_s = twin.timed(n_twin)
    twin.params = twin.opt_state = None
    drv.steps, drv.failed = steps, failed

    ctx = {
        "events": events, "chips": run.chips,
        "flops_per_token": reference_model(run.cell).flops_per_token(
            run.sz, run.seq),
        "tokens": n_trace * tokens_per_chunk,
        "peaks": counts.peaks(jax.devices()[0].device_kind),
        "work": work, "log": log,
        "mkor_chunk_s": mkor_chunk_s, "lamb_chunk_s": lamb_chunk_s,
        "mkor_state_bytes": mkor_state, "lamb_state_bytes": lamb_state,
    }
    metrics = {}
    for m in run.cell["per_layer"]:
        mod = load_module(BENCH / "metrics" / f"{m['name']}.py")
        value = mod.read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    log(f"trace window_host_s={window_host_s:.3f} mkor_chunk_s="
        f"{mkor_chunk_s:.4f} lamb_chunk_s={lamb_chunk_s:.4f}")
    busy, window = tracefile.busy_and_window(events)
    extra = {"device": {"busy_s": busy, "window_s": window},
             "breakdown": tracefile.breakdown(events)}
    return metrics, extra, peak, compiles
