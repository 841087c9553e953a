"""A cell of the benchmark: found by name, built, run, and checked.

Everything here is driven by files that are found by the names in
``BENCHMARK.json``; no list of cells, configurations or metrics lives in
code:

* ``configs/<config>.json`` holds the configuration as it is run, and names
  beside it the Python module with the density as its user writes it, the
  plain reference, the start states and the work of one evaluation;
* ``workloads/<traffic>.json`` holds a traffic mix: chains, trajectories per
  call, the pool the chains are drawn from, the calls compared and traced;
* ``limits/<cell>.json`` holds the limits of the numbers that decide
  ``correct``, with the readings they were set from;
* ``metrics/<metric>.py`` reads one per-layer metric;
* ``peaks.json`` holds each device kind's peaks, with their source.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
#: JAX's persistent compilation cache: a fixed path inside the checkout,
#: so that every run of a cell after the first finds its programs.
CACHE_DIR = HERE / ".jax_cache"
LOGP_SCOPE = "bench.logp"
CALL_SPAN = "bench.call"


def read_json(path: Path):
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    name = "chipbench_" + path.stem.replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark(root: Path = ROOT) -> dict:
    return read_json(root / "BENCHMARK.json")


def entry(bench: dict, cell: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == cell:
            return w
    raise KeyError(f"no workload {cell!r} in BENCHMARK.json")


def config(bench: dict, name: str):
    """``(configuration dict, its module)``."""
    for c in bench["configs"]:
        if c["name"] == name:
            path = ROOT / c["file"]
            cfg = read_json(path)
            return cfg, load_module(path.parent / cfg["module"])
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name: str) -> dict:
    return read_json(HERE / "workloads" / f"{name}.json")


def limits(cell: str) -> dict:
    return read_json(HERE / "limits" / f"{cell}.json")


def peaks(kind: str) -> dict:
    table = read_json(HERE / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in chipbench/peaks.json")
    return table[kind]


def peak_flops(pk: dict, precision: str) -> float:
    """The chip's peak for float32 matrix products at ``precision``: its
    bfloat16 peak over the bfloat16 passes that one product takes."""
    return pk["flops_per_s"] / pk["bf16_passes"][precision]


def readers(bench: dict, cell: str) -> list:
    """``(metric entry, reader module)`` for each per-layer metric of the
    cell: those that list it, and those that list no cells at all."""
    out = []
    for m in bench["per_layer"]:
        cells = m.get("workloads")
        if cells is not None and cell not in cells:
            continue
        out.append((m, load_module(HERE / "metrics" / f"{m['name']}.py")))
    return out


class CompileClock:
    """Seconds JAX spent compiling (cache reads included), compilations,
    and persistent-cache hits, from ``jax.monitoring`` events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def scoped(logp):
    """The user's density under ``jax.named_scope("bench.logp")``, so that
    its operations, the transposed gradient's among them, carry a name in
    the device trace whatever the VM does to its blocks."""
    import jax

    def logp_scoped(x):
        with jax.named_scope(LOGP_SCOPE):
            return logp(x)

    return logp_scoped


@dataclasses.dataclass
class Cell:
    name: str
    cfg: dict
    module: object
    traffic: dict
    chips: int
    chains: int
    traj: int
    eps: float
    theta0: np.ndarray
    keys: np.ndarray  # [chains, 2] uint32 base keys
    kernel: object = None
    fold: object = None
    start: object = None  # device theta after the warm-up call

    @property
    def settings(self) -> dict:
        return dict(max_tree_depth=self.cfg["max_tree_depth"],
                    steps_per_leaf=self.cfg["steps_per_leaf"],
                    num_steps=self.traj)

    @property
    def grads_per_leaf(self) -> int:
        return self.cfg["steps_per_leaf"] + 1

    def call_keys(self, index: int):
        import jax.numpy as jnp

        return self.fold(jnp.asarray(self.keys), jnp.uint32(index))


def inputs(cfg, module, tr: dict, seed: int):
    """Start states and base keys of every chain.

    The chains are drawn from a pool fixed by the traffic's ``pool_seed``
    and dealt to the lanes in an order drawn from ``seed``: every seed
    gets the same work, in another order.
    """
    n = tr["chains"]
    pool = np.random.default_rng(tr["pool_seed"])
    theta = module.start_states(cfg, n, pool)
    keys = pool.integers(0, 2**32, size=(n, 2), dtype=np.uint32)
    order = np.random.default_rng(seed).permutation(n)
    return theta[order], keys[order]


def build(bench: dict, cell: str, seed: int, *, traffic_over=None,
          config_over=None) -> Cell:
    """Load the cell's files, make its inputs from ``seed`` and build the
    program's kernel; nothing is compiled yet."""
    import jax
    from repro.mcmc import nuts

    w = entry(bench, cell)
    cfg, module = config(bench, w["config"])
    cfg = {**cfg, **(config_over or {})}
    tr = {**traffic(w["traffic"]), **(traffic_over or {})}
    theta0, keys = inputs(cfg, module, tr, seed)
    # The configuration's matrix-product precision holds for the whole
    # run: the program, and the reference that checks it.
    precision = cfg["matmul_precision"]
    jax.config.update("jax_default_matmul_precision",
                      None if precision == "default" else precision)
    c = Cell(cell, cfg, module, tr, w["chips"], tr["chains"],
             tr["trajectories_per_call"], cfg["step_size"], theta0, keys)
    target = module.program_target(cfg)
    target = dataclasses.replace(target, logp=scoped(target.logp))
    c.kernel = nuts.make_nuts_kernel(
        target,
        nuts.NutsSettings(max_tree_depth=cfg["max_tree_depth"],
                          num_steps=c.traj,
                          steps_per_leaf=cfg["steps_per_leaf"]),
        backend="pc", mesh=c.chips if c.chips > 1 else None)
    c.fold = jax.jit(jax.vmap(jax.random.fold_in, in_axes=(0, None)))
    return c


def warm_up(c: Cell) -> None:
    """One call at the cell's own shapes: call index 0, whose draws start
    the window."""
    import jax
    import jax.numpy as jnp

    out = c.kernel(jnp.asarray(c.theta0), jnp.float32(c.eps), c.call_keys(0))
    c.start = jax.block_until_ready(out)["theta"]


@dataclasses.dataclass
class Window:
    seconds: float  # from the first call's start to the last call's end
    calls: list  # per call: index, theta in, outputs, counters, seconds
    compiles: int
    traced: int  # the first ``traced`` calls ran under the profiler


def run_window(c: Cell, seconds: float, clock: CompileClock, *,
               trace_dir: str | None = None) -> Window:
    """Calls back to back for ``seconds``; each call's draws start the next
    call's chains, and its keys are the base keys folded with its index."""
    import jax
    import jax.numpy as jnp

    eps = jnp.float32(c.eps)
    theta = c.start
    n_trace = c.traffic["trace_calls"] if trace_dir else 0
    calls = []
    compiles0 = clock.compiles
    if n_trace:
        jax.profiler.start_trace(trace_dir)
    t0 = time.perf_counter()
    deadline = t0 + seconds
    index = 1
    while True:
        start = time.perf_counter()
        with jax.profiler.TraceAnnotation(CALL_SPAN):
            keys = c.call_keys(index)
            out = jax.block_until_ready(c.kernel(theta, eps, keys))
        res = c.kernel.last_result
        calls.append(SimpleNamespace(
            index=index, theta_in=theta, out=out, steps=res.steps,
            tag_stats=dict(res.tag_stats), converged=res.converged,
            depth_exceeded=res.depth_exceeded, fault_code=res.fault_code,
            seconds=time.perf_counter() - start))
        theta = out["theta"]
        if n_trace and index == n_trace:
            jax.profiler.stop_trace()
        index += 1
        if time.perf_counter() >= deadline and index > n_trace:
            break
    t1 = time.perf_counter()
    return Window(t1 - t0, calls, clock.compiles - compiles0, n_trace)


def counters(c: Cell, win: Window) -> list[dict]:
    """Per call: VM dispatches, leaf executions and active leaf lanes, and
    the chains that faulted (read after the window, never inside it)."""
    import jax

    out = []
    for call in win.calls:
        steps, conv, dex, fc, theta = jax.device_get(
            (call.steps, call.converged, call.depth_exceeded,
             call.fault_code, call.out["theta"]))
        bad = ~np.all(np.isfinite(theta), axis=1)
        if dex is not None:
            bad |= np.asarray(dex, bool)
        if fc is not None:
            bad |= np.asarray(fc) != 0
        if not bool(conv):
            bad[:] = True
        execs, active = call.tag_stats.get("grad", (0, 0))
        out.append({"steps": int(steps), "grad_execs": int(execs),
                    "grad_active": int(active), "failed": int(bad.sum())})
    return out


def sample_calls(win: Window, seed: int, k: int) -> list:
    """``k`` calls of the window, drawn from the seed."""
    rng = np.random.default_rng([seed, 2])
    pick = rng.choice(len(win.calls), size=min(k, len(win.calls)),
                      replace=False)
    return [win.calls[i] for i in sorted(pick)]


def fetch(c: Cell, calls: list) -> list[dict]:
    """Host copies of what the compared calls took and gave."""
    import jax

    return [{"index": call.index,
             "theta_in": np.asarray(jax.device_get(call.theta_in)),
             "keys": np.asarray(jax.device_get(c.call_keys(call.index))),
             "out": {k: np.asarray(v)
                     for k, v in jax.device_get(call.out).items()}}
            for call in calls]


def reference_runner(c: Cell, control: bool = False):
    import nutsref

    logp, grad = c.module.reference(c.cfg, control=control)
    return nutsref.make_runner(logp, grad, c.cfg["dim"], **c.settings)


def reference_outputs(c: Cell, sample: list[dict], runner,
                      seconds: list | None = None) -> list[dict]:
    """The reference's draws for each compared call, on the first device;
    the seconds each took are appended to ``seconds``."""
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    outs = []
    for s in sample:
        t = time.perf_counter()
        out = jax.device_get(runner(
            jax.device_put(jnp.asarray(s["theta_in"]), dev),
            jnp.float32(c.eps), jax.device_put(jnp.asarray(s["keys"]), dev)))
        outs.append({k: np.asarray(v) for k, v in out.items()})
        if seconds is not None:
            seconds.append(time.perf_counter() - t)
    return outs


def chain_gaps(got: dict, ref: dict) -> np.ndarray:
    """Per chain: the largest difference from the reference over every
    output and coordinate, in units of that coordinate's spread over the
    reference's chains (about its posterior standard deviation)."""
    gap = None
    for k, r in ref.items():
        scale = r.std(axis=0)
        scale = np.where(scale > 0, scale, 1.0)
        g = np.abs(np.asarray(got[k], np.float64) - r) / scale
        g = np.where(np.isfinite(g), g, np.inf).max(axis=1)
        gap = g if gap is None else np.maximum(gap, g)
    return gap


def numbers(gaps: np.ndarray) -> dict:
    """The numbers compared with their limits: the median and the 90th
    percentile of the chains' gaps, for faults that touch many chains, and
    the widest gap, for a fault in a single chain."""
    return {"gap_p50": float(np.quantile(gaps, 0.5)),
            "gap_p90": float(np.quantile(gaps, 0.9)),
            "gap_max": float(gaps.max())}


def judge(values: dict, lim: dict) -> tuple[bool, dict]:
    checks = {}
    ok = True
    for name, value in values.items():
        limit = lim[name]["limit"]
        good = math.isfinite(value) and value <= limit
        ok &= good
        checks[name] = {"value": value, "limit": limit}
    return ok, checks
