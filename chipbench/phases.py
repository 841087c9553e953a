"""Time each named phase of a cell's calls on the chip.

    python chipbench/phases.py --workload <cell> --seed <n> [--trace 1]
        [--windows K] [--seconds S] [--out FILE] [--hlo FILE]
        [--record FILE --chains N --depth D]

From the root of a checkout, on a machine whose JAX sees TPU chips.  Sets
the cell up as ``run.py`` does, then

* with ``--trace 1``: traces the cell's first ``trace_calls`` calls and
  reduces the trace twice, with ``tracereduce`` (the benchmark's readings)
  and with ``phasereduce`` (the loop's busy time by scope, its idle gaps,
  the idle time outside it by host phase, and the clock check), and
  prints how the two account for the same idle and busy time;
* runs ``--windows`` untraced windows of ``--seconds`` each and logs every
  call's seconds with the host phases the program recorded for it
  (``SchedulerStats.host_phases``).  Every window makes the same calls, so
  a call 0.08 s or more above the median of its own index is a stall, and
  its phases say whether the host or the device held it; the traced calls
  against the same untraced calls give the cost of tracing;
* with ``--hlo``: writes the loop program's optimized HLO text, gzipped;
* with ``--record``: traces two calls of a small cut of the cell
  (``--chains`` chains, tree depth ``--depth``) and writes the trace,
  pruned to what the reductions read, as a test fixture.

The last line on standard output is a JSON summary; ``--out`` gets all of
it.  A program without the phases (an older one) runs too: its calls log
no phases and its trace reads no split.
"""
from __future__ import annotations

import run  # noqa: I001  (sets up sys.path and the process clock)

import argparse
import glob
import gzip
import json
import re
import shutil
import statistics
import sys
import tempfile
import time
from collections import Counter
from types import SimpleNamespace

import cell
import phasereduce
import tracereduce
import xplane

STALL_S = 0.08


class Recorder:
    """The cell's kernel, keeping the host phases of each call."""

    def __init__(self, kernel):
        self.kernel = kernel
        self.phases = []

    def __call__(self, *args):
        out = self.kernel(*args)
        sched = self.kernel.last_result.sched
        self.phases.append(dict(getattr(sched, "host_phases", {})))
        return out

    @property
    def last_result(self):
        return self.kernel.last_result


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--windows", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out")
    ap.add_argument("--hlo")
    ap.add_argument("--record")
    ap.add_argument("--chains", type=int, default=64)
    ap.add_argument("--depth", type=int, default=4)
    return ap.parse_args(argv)


def loop_hlo(c) -> str:
    """The optimized HLO text of the VM loop program the kernel runs."""
    import jax
    import jax.numpy as jnp

    k = c.kernel.kernel
    inputs, _ = k._bind((jnp.asarray(c.theta0), jnp.float32(c.eps),
                         c.call_keys(0)))
    ex = k._last_executor
    state = jax.eval_shape(ex.vm._start, ex._qualify(inputs))
    return ex.vm._jitted_loop.lower(state).compile().as_text()


def prune(src: str, dst: str) -> None:
    """Copy a trace keeping what the reductions read: device operations'
    category and op name (asynchronous ones only where they are
    collectives), and the host spans they name."""
    sp = xplane.load(src)
    keep_stats = {"hlo_category", "tf_op"}
    for plane in sp.planes:
        if tracereduce.DEVICE_PLANE.match(plane.name):
            names = {k: v.name for k, v in plane.stat_metadata.items()}
            coll = set()
            for k, em in plane.event_metadata.items():
                kept = [s for s in em.stats
                        if names.get(s.metadata_id) in keep_stats]
                del em.stats[:]
                em.stats.extend(kept)
                text = em.name + " " + " ".join(s.str_value for s in kept)
                if tracereduce.COLLECTIVE.search(text):
                    coll.add(k)
            for line in plane.lines:
                if line.name == "Async XLA Ops":
                    kept = [ev for ev in line.events if ev.metadata_id in coll]
                    del line.events[:]
                    line.events.extend(kept)
                for ev in line.events:
                    del ev.stats[:]
            del plane.stats[:]
        elif plane.name == tracereduce.HOST_PLANE:
            meta = {k: v.name for k, v in plane.event_metadata.items()}
            wanted = {k for k, n in meta.items()
                      if n == cell.CALL_SPAN
                      or n.startswith(phasereduce.PREFIXES)}
            for line in plane.lines:
                kept = [ev for ev in line.events if ev.metadata_id in wanted]
                del line.events[:]
                line.events.extend(kept)
                for ev in line.events:
                    del ev.stats[:]
            for k in [k for k in plane.event_metadata if k not in wanted]:
                del plane.event_metadata[k]
            del plane.stats[:]
    planes = [p for p in sp.planes
              if tracereduce.DEVICE_PLANE.match(p.name)
              or p.name == tracereduce.HOST_PLANE]
    del sp.planes[:]
    sp.planes.extend(planes)
    with open(dst, "wb") as f:
        f.write(sp.SerializeToString())


def trace_window(c, clock, read):
    """Trace the cell's ``trace_calls`` calls; ``(window, read(path of the
    trace file))``."""
    trace_dir = tempfile.mkdtemp(prefix="phases-")
    try:
        win = cell.run_window(c, 0.0, clock, trace_dir=trace_dir)
        path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
        return win, read(path)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def traced(c, clock, bench, workload) -> dict:
    """One traced window, read by both reductions."""
    win, (red, ph) = trace_window(c, clock, lambda path: (
        tracereduce.reduce(path, call_span=cell.CALL_SPAN),
        phasereduce.reduce(path)))
    per_call = cell.counters(c, win)
    ctx = SimpleNamespace(
        cell=c, chips=c.chips, chains=c.chains, traj=c.traj,
        grads_per_leaf=c.grads_per_leaf, calls=per_call, traced=per_call,
        trace=red, peaks=None, peak_flops=None,
        work=lambda chains: c.module.work(c.cfg, chains))
    old = {}
    for m, reader in cell.readers(bench, workload):
        if m["name"] in ("logp_roofline", "mfu.nuts"):
            continue  # need the chip's peaks; run.py reads them
        old[m["name"]] = reader.read(ctx)
    steps = sum(p["steps"] for p in per_call)
    calls = len(win.calls)
    new = phasereduce.metrics(ph, steps)
    window_s = red.window_ps / 1e12
    idle_s = window_s * old["idle_share.nuts"] / 100
    loop_idle_s = ph.loop_idle_ps / 1e12
    host_idle_s = sum(ph.host_gaps.values()) / 1e12
    seconds = [call.seconds for call in win.calls]
    kinds = Counter()  # "%copy.12 = f32[...] copy(...)" -> "copy"
    for name, ps in ph.unscoped_ops.items():
        kinds[re.sub(r"[.\d]+$", "", name.split(" ")[0].lstrip("%"))] += ps
    out = {
        "calls": calls, "steps": steps, "window_s": window_s,
        "call_seconds": seconds,
        "traced_draws_per_s": c.chains * c.traj * calls / sum(seconds),
        "benchmark_metrics": old, "new_metrics": new,
        "breakdown": phasereduce.breakdown(ph),
        "loop_module": ph.loop,
        "loop_busy_us_per_dispatch": {
            k: v / 1e6 / steps for k, v in ph.loop_busy.most_common()},
        "unscoped_ops_s": [[k, v / 1e12]
                           for k, v in ph.unscoped_ops.most_common(15)],
        "unscoped_kinds_s": {k: v / 1e12 for k, v in kinds.most_common()},
        "host_spans": dict(ph.host_spans),
        "accounting": {
            "idle_s": idle_s,
            "loop_idle_plus_host_gap_s": loop_idle_s + host_idle_s,
            "host_gap_ms": old["host_gap_ms.nuts"],
            "host_gaps_ms_per_call": {
                k: v / 1e9 / calls for k, v in ph.host_gaps.most_common()},
            "us_per_dispatch": old["us_per_dispatch.nuts"],
            "loop_busy_us_per_dispatch": sum(ph.loop_busy.values())
            / 1e6 / steps,
        },
    }
    if ph.clock_margins:
        out["clock_margin_us"] = phasereduce.check_clock(ph) / 1e6
        run.log(f"clock check: smallest margin "
                f"{out['clock_margin_us']:.3f} us over "
                f"{len(ph.clock_margins)} calls")
    return out


def windows(c, clock, k: int, seconds: float) -> dict:
    """``k`` untraced windows; every call's seconds and host phases."""
    rows = []
    draws = []
    for w in range(k):
        first = len(c.kernel.phases)
        win = cell.run_window(c, seconds, clock)
        draws.append(c.chains * c.traj * len(win.calls) / win.seconds)
        for call, ph in zip(win.calls, c.kernel.phases[first:]):
            rows.append({"window": w, "index": call.index,
                         "seconds": call.seconds, "phases": ph})
            run.log(f"window {w} call {call.index}: {call.seconds:.4f} s "
                    + " ".join(f"{n}={v:.4f}" for n, v in ph.items()))
    if not rows:
        return {}
    med = {i: statistics.median(r["seconds"] for r in rows if r["index"] == i)
           for i in {r["index"] for r in rows}}
    stalls = []
    for r in rows:
        r["excess_s"] = r["seconds"] - med[r["index"]]
        if r["excess_s"] < STALL_S:
            continue
        # The phase that took longest beyond its median over all calls.
        over = {n: v - statistics.median(x["phases"].get(n, 0.0)
                                         for x in rows)
                for n, v in r["phases"].items()}
        r["held_by"] = max(over, key=over.get) if over else None
        stalls.append(r)
        run.log(f"stall: window {r['window']} call {r['index']} took "
                f"{r['seconds']:.4f} s, {r['excess_s']:.4f} s over its "
                f"median; held in {r['held_by']}")
    return {"windows": k, "calls": len(rows), "median_call_s": med,
            "draws_per_s": draws, "stalls": stalls, "rows": rows}


def main(argv=None) -> int:
    args = parse(argv)
    bench = cell.benchmark()
    w = cell.entry(bench, args.workload)
    run.enable_cache()
    try:
        run.find_devices(w["chips"], require_tpu=True)
    except run.Refused as e:
        run.log(f"refused: {e}")
        return 2
    clock = cell.CompileClock()
    summary = {"workload": args.workload, "seed": args.seed}
    if args.record:
        c = cell.build(bench, args.workload, args.seed,
                       traffic_over={"chains": args.chains,
                                     "trace_calls": 2},
                       config_over={"max_tree_depth": args.depth})
        cell.warm_up(c)
        trace_window(c, clock, lambda path: prune(path, args.record))
        ph = phasereduce.reduce(args.record)
        summary["record"] = {"file": args.record,
                             "clock_margins_ps": ph.clock_margins,
                             "host_spans": dict(ph.host_spans)}
    else:
        c = cell.build(bench, args.workload, args.seed)
        c.kernel = Recorder(c.kernel)
        cell.warm_up(c)
        summary["setup_s"] = time.perf_counter() - run.T0
        run.log(f"setup: {summary['setup_s']:.3f} s")
        if args.hlo:
            with gzip.open(args.hlo, "wt") as f:
                f.write(loop_hlo(c))
            summary["hlo"] = args.hlo
        if args.trace:
            summary["traced"] = traced(c, clock, bench, args.workload)
        if args.windows:
            summary["untraced"] = u = windows(c, clock, args.windows,
                                              args.seconds)
            if args.trace:
                t = summary["traced"]
                same = sum(u["median_call_s"][i + 1]
                           for i in range(t["calls"]))
                # Seconds of the traced calls over the same calls untraced.
                summary["tracing_cost"] = sum(t["call_seconds"]) / same
    if args.out:
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=1)
    brief = dict(summary)
    if "untraced" in brief:
        brief["untraced"] = {k: v for k, v in brief["untraced"].items()
                             if k != "rows"}
    print(json.dumps(brief), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
