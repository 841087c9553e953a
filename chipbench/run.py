"""Run one cell of the chip benchmark once.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout, on a machine whose JAX sees TPU chips.  It

1. sets up: loads the cell's files by name (``cell.py``), turns on JAX's
   persistent compilation cache at ``chipbench/.jax_cache``, makes the
   chains' start states and keys from ``--seed``, builds the program's
   many-chain NUTS kernel and warms it up with one call at the cell's own
   shapes.  ``setup_s`` runs from process start to the end of that;
2. measures: calls the kernel back to back for ``--seconds``; each call's
   draws start the next call's chains.  ``draws_per_s`` is the draws of
   every call in the window over the time from its start to the end of
   its last call.  With ``--trace 1`` the profiler records the window's
   first calls and the per-layer metrics are read from that trace and
   from the program's counters instead;
3. checks: after the window, with the program's state let go, the plain
   reference (``nutsref.py``) runs on the inputs of calls drawn from the
   seed; each chain's draws are compared with it (``cell.chain_gaps``),
   and every number compared is printed beside its limit.

The last line on standard output is the result as one JSON object.  Off a
TPU, or with fewer chips than the cell needs, it exits 2 and prints none.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

import cell  # noqa: E402


class Refused(RuntimeError):
    """The run cannot measure this cell here; nothing is printed."""


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def enable_cache() -> None:
    import jax

    jax.config.update("jax_compilation_cache_dir", str(cell.CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def find_devices(chips: int, require_tpu: bool):
    import jax

    devices = jax.devices()
    d0 = devices[0]
    if require_tpu and d0.platform != "tpu":
        raise Refused(f"JAX found no TPU (platform {d0.platform!r})")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips; JAX sees "
                      f"{len(devices)}")
    return devices


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def breakdown(reduced) -> dict:
    d = reduced.fullest()
    return {
        "device_ops": [[k, v / 1e12] for k, v in d.ops.most_common(10)],
        "idle_gaps": [[k, v / 1e12] for k, v in
                      sorted(reduced.gaps, key=lambda g: -g[1])[:10]],
    }


def run(args, *, require_tpu: bool = True, traffic_over=None,
        config_over=None, plant=None) -> dict:
    """One run of one cell; returns the result object.

    ``plant(cell)`` may replace the cell's kernel after it is built: the
    tests use it to break the timed path and see ``correct`` turn false.
    """
    bench = cell.benchmark()
    w = cell.entry(bench, args.workload)
    enable_cache()
    devices = find_devices(w["chips"], require_tpu)
    kind = devices[0].device_kind
    pk = None
    if require_tpu:
        try:
            pk = cell.peaks(kind)
        except KeyError as e:
            raise Refused(str(e)) from None
    used = devices[: w["chips"]]
    clock = cell.CompileClock()

    c = cell.build(bench, args.workload, args.seed,
                   traffic_over=traffic_over, config_over=config_over)
    if plant is not None:
        plant(c)
    cell.warm_up(c)
    setup_s = time.perf_counter() - T0
    log(f"setup: {setup_s:.3f} s ({clock.compiles} compiles, "
        f"{clock.seconds:.3f} s compiling, {clock.hits} cache hits)")

    trace_dir = tempfile.mkdtemp(prefix="chipbench-") if args.trace else None
    try:
        win = cell.run_window(c, args.seconds, clock, trace_dir=trace_dir)
        memory = memory_peak(used)
        per_call = cell.counters(c, win)
        sample = cell.fetch(c, cell.sample_calls(
            win, args.seed, c.traffic["compare_calls"]))
        reduced = None
        if trace_dir:
            import tracereduce

            files = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
            reduced = tracereduce.reduce(files[0], call_span=cell.CALL_SPAN)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    log(f"window: {len(win.calls)} calls in {win.seconds:.6f} s; "
        f"compiles in the window: {win.compiles}; seconds per call "
        f"{[round(call.seconds, 4) for call in win.calls]}")
    n_calls = len(win.calls)
    draws = c.chains * c.traj * n_calls
    traced = per_call[: win.traced]
    # Let the program's state go before the reference runs.
    win.calls.clear()
    c.kernel = c.start = None

    ref_s = []
    ref = cell.reference_outputs(c, sample, cell.reference_runner(c), ref_s)
    gaps = np.concatenate([cell.chain_gaps(s["out"], r)
                           for s, r in zip(sample, ref)])
    values = cell.numbers(gaps)
    correct, checks = cell.judge(values, cell.limits(args.workload))
    log(f"reference: calls {[s['index'] for s in sample]} of {n_calls}, "
        f"{len(gaps)} chains, seconds per call {[round(t, 3) for t in ref_s]}")

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": memory}
    result = {"correct": bool(correct), "attempted": draws,
              "failed": sum(p["failed"] for p in per_call) * c.traj}
    if args.trace:
        ctx = SimpleNamespace(
            cell=c, chips=c.chips, chains=c.chains, traj=c.traj,
            grads_per_leaf=c.grads_per_leaf, calls=per_call, traced=traced,
            trace=reduced, peaks=pk,
            peak_flops=(None if pk is None else
                        cell.peak_flops(pk, c.cfg["matmul_precision"])),
            work=lambda chains: c.module.work(c.cfg, chains))
        metrics = {}
        for m, reader in cell.readers(bench, args.workload):
            value = reader.read(ctx)
            if value is None:
                continue
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            note = getattr(reader, "note", None)
            if note is not None:
                metrics[m["name"]]["note"] = note(ctx)
        busy = [d.busy_ps for d in reduced.devices if d.busy_ps > 0]
        device["busy_s"] = sum(busy) / len(busy) / 1e12
        device["window_s"] = reduced.window_ps / 1e12
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = breakdown(reduced)
    else:
        e2e = {m["name"]: m for m in bench["end_to_end"]}
        result["metrics"] = {
            "draws_per_s": {"value": draws / win.seconds,
                            "unit": e2e["draws_per_s"]["unit"]},
            "setup_s": {"value": setup_s, "unit": e2e["setup_s"]["unit"]},
        }
        result["device"] = device
    result["checks"] = checks
    for name, chk in checks.items():
        log(f"check {name}: {chk['value']!r} (limit {chk['limit']!r})")
    return result


def main(argv=None) -> int:
    args = parse(argv)
    try:
        result = run(args)
    except Refused as e:
        log(f"refused: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
