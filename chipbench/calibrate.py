"""Readings that the limits in ``limits/<cell>.json`` are set from.

    python chipbench/calibrate.py --workload <cell> --seeds 1,2,... \
        --control-seeds 1,2,3 --seconds <s> [--compare N|all] [--out DIR]

In one process, so that the cell compiles once: for each seed, a short
window of the program at the cell's own size and load, then the numbers
that decide ``correct`` (``cell.numbers``) against the plain reference,
over ``--compare`` calls of the window drawn from the seed (the traffic's
``compare_calls`` unless given; ``all`` compares every call).  For each
control seed, the same calls' inputs are also run through the reference
computed one precision lower (the configuration's
``reference(cfg, control=True)``) in the program's place, and that
control is held to the same numbers: it has to read as not correct.  On
the same seeds the program's own draws are read with a fault in one
chain, the last lane: returned unchanged, or moved by a tenth of each
coordinate's spread.

The benchmark's own runs never run this.  It prints one JSON line per
reading, and writes them to ``DIR/<cell>.jsonl`` when ``--out`` is given.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

import numpy as np  # noqa: E402

import cell  # noqa: E402
import run as runner  # noqa: E402


def one_lane(sample: list[dict], fault: str, traj: int) -> list[dict]:
    """The program's draws with a fault in the last chain."""
    outs = []
    for s in sample:
        out = {k: v.copy() for k, v in s["out"].items()}
        theta = out["theta"]
        if fault == "unchanged":
            th = s["theta_in"][-1]
            theta[-1] = th
            out["sum_theta"][-1] = traj * th
            out["sum_sq"][-1] = traj * th * th
        elif fault == "altered":
            theta[-1] += 0.1 * theta.std(axis=0)
        else:
            raise ValueError(fault)
        outs.append(out)
    return outs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--compare", default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = {int(s) for s in args.control_seeds.split(",") if s}

    bench = cell.benchmark()
    runner.enable_cache()
    w = cell.entry(bench, args.workload)
    devices = runner.find_devices(w["chips"], require_tpu=True)
    clock = cell.CompileClock()
    c = cell.build(bench, args.workload, seeds[0])
    stated = cell.reference_runner(c)
    control = cell.reference_runner(c, control=True)
    rows = []
    for seed in seeds:
        c.theta0, c.keys = cell.inputs(c.cfg, c.module, c.traffic, seed)
        t = time.perf_counter()
        cell.warm_up(c)
        warm = time.perf_counter() - t
        win = cell.run_window(c, args.seconds, clock)
        k = (len(win.calls) if args.compare == "all" else
             int(args.compare or c.traffic["compare_calls"]))
        sample = cell.fetch(c, cell.sample_calls(win, seed, k))
        steps = [int(x.steps) for x in win.calls]
        win.calls.clear()
        ref = cell.reference_outputs(c, sample, stated)
        gaps = np.concatenate([cell.chain_gaps(s["out"], r)
                               for s, r in zip(sample, ref)])
        row = {"seed": seed, "side": "program", **cell.numbers(gaps),
               "diverged_share": float(np.mean(gaps > 0.1)),
               "gap_max": float(gaps.max()), "calls": len(steps),
               "window_s": win.seconds, "warm_s": warm,
               "draws_per_s": c.chains * c.traj * len(steps) / win.seconds,
               "steps": steps, "compiles_in_window": win.compiles,
               "compared": [x["index"] for x in sample]}
        rows.append(row)
        print(json.dumps(row), flush=True)
        if seed in control_seeds:
            low = cell.reference_outputs(c, sample, control)
            gaps = np.concatenate([cell.chain_gaps(lo, r)
                                   for lo, r in zip(low, ref)])
            row = {"seed": seed, "side": "control", **cell.numbers(gaps),
                   "diverged_share": float(np.mean(gaps > 0.1)),
                   "gap_max": float(gaps.max())}
            rows.append(row)
            print(json.dumps(row), flush=True)
            for fault in ("unchanged", "altered"):
                bad = one_lane(sample, fault, c.traj)
                gaps = np.concatenate([cell.chain_gaps(b, r)
                                       for b, r in zip(bad, ref)])
                row = {"seed": seed, "side": f"one_lane_{fault}",
                       **cell.numbers(gaps)}
                rows.append(row)
                print(json.dumps(row), flush=True)
    for side in ("program", "control", "one_lane_unchanged",
                 "one_lane_altered"):
        got = [r for r in rows if r.get("side") == side]
        if got:
            print(side, {k: (min(r[k] for r in got), max(r[k] for r in got))
                         for k in ("gap_p50", "gap_p90", "gap_max")},
                  flush=True)
    print(f"device {devices[0].device_kind}; compile {clock.seconds:.1f} s",
          flush=True)
    if args.out:
        out = Path(args.out)
        out.mkdir(parents=True, exist_ok=True)
        with open(out / f"{args.workload}.jsonl", "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
