"""Benchmark driver: ``PYTHONPATH=src python -m benchmarks.run``.

Runs one benchmark per paper table/figure (quick CPU configurations;
pass ``--full`` for paper-scale) plus the framework-level benches, and
renders the roofline table from any dry-run artifacts present.

  fig5   NUTS gradient throughput vs batch size (paper Fig. 5)
  fig6   batch utilization across recursion (paper Fig. 6)
  serve  VM-scheduled generation engine throughput
  roofline  per-(arch x shape x mesh) terms from dry-run artifacts
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from pathlib import Path

from repro.launch import compile_cache

from . import fig5_throughput, fig6_utilization, roofline, serve_bench
from .common import validate_bench_json

#: Default BENCH_*.json artifacts land at the repo root regardless of
#: the invoking cwd, so the perf-trajectory records tracked across PRs
#: always live in one place.
REPO_ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma list: fig5,fig6,serve,roofline")
    ap.add_argument("--batches", default=None,
                    help="comma-separated batch sizes for fig5/fig6")
    ap.add_argument("--mesh", default=None,
                    help="comma list of lane-sharding device counts for the "
                         "fig5/serve pc arms (e.g. 'none,8'; requires that "
                         "many visible devices)")
    ap.add_argument("--schedule", default=None,
                    help="comma list of pc schedules for fig5 (earliest, "
                         "popular, sweep, lookahead); default earliest")
    ap.add_argument("--compact-every", default=None,
                    help="comma list of lane-compaction cadences for the "
                         "fig5 pc arms (e.g. 'none,1')")
    ap.add_argument("--use-kernel", default=None,
                    help="comma list of on/off: Pallas stack kernels for "
                         "the fig5 pc arms")
    ap.add_argument("--pgo", default=None,
                    help="comma list of on/off: profile-guided re-lowering "
                         "for the fig5 pc arms (e.g. 'on,off')")
    ap.add_argument("--per-device-batch", action="store_true",
                    help="fig5: treat --batches as per-device (mesh arms "
                         "scale total batch by device count)")
    ap.add_argument("--serve-arrivals", default="closed",
                    choices=("closed", "poisson"),
                    help="serve bench mode: closed-loop sweep or open-loop "
                         "Poisson continuous batching")
    ap.add_argument("--serve-requests", type=int, default=16,
                    help="open-loop serve: requests in the arrival stream")
    ap.add_argument("--json-out", default=str(REPO_ROOT / "BENCH_fig5.json"),
                    help="path for the machine-readable fig5 results "
                         "(tracked across PRs; default: repo root); empty "
                         "string disables")
    ap.add_argument("--serve-json-out",
                    default=str(REPO_ROOT / "BENCH_serve.json"),
                    help="path for the machine-readable serve results "
                         "(default: repo root); empty string disables")
    args = ap.parse_args(argv)
    print(f"[compile cache: {compile_cache.enable(REPO_ROOT)}]")
    only = set(args.only.split(",")) if args.only else None

    def want(name: str) -> bool:
        return only is None or name in only

    common = (["--full"] if args.full else []) + (
        ["--batches", args.batches] if args.batches else []
    )
    emitted: list[str] = []  # artifacts THIS run wrote (validated below)
    t0 = time.time()
    if want("fig5"):
        print()
        # Measure the fused pc arm against the unfused/earliest seed
        # baseline in the same run, and persist the records.
        fig5_args = common + ["--fuse", "on,off"]
        if args.schedule:
            fig5_args += ["--schedule", args.schedule]
        if args.compact_every:
            fig5_args += ["--compact-every", args.compact_every]
        if args.use_kernel:
            fig5_args += ["--use-kernel", args.use_kernel]
        if args.pgo:
            fig5_args += ["--pgo", args.pgo]
        if args.mesh:
            fig5_args += ["--mesh", args.mesh]
            if args.per_device_batch:
                fig5_args += ["--per-device-batch"]
        if args.json_out:
            fig5_args += ["--json", args.json_out]
            emitted.append(args.json_out)
        fig5_throughput.main(fig5_args)
    if want("fig6"):
        print()
        fig6_utilization.main(common)
    if want("serve"):
        print()
        serve_args = ["--arrivals", args.serve_arrivals]
        if args.serve_arrivals == "poisson":
            serve_args += ["--num-requests", str(args.serve_requests)]
        if args.mesh:
            # serve_bench takes a single device count: use the largest.
            counts = [m for m in args.mesh.split(",")
                      if m.strip().lower() not in ("none", "0")]
            if counts:
                serve_args += ["--mesh", max(counts, key=int)]
        if args.serve_json_out:
            serve_args += ["--json", args.serve_json_out]
            emitted.append(args.serve_json_out)
        serve_bench.main(serve_args)
    if want("roofline"):
        print()
        roofline.main([])
        print()
        roofline.main(["--mesh", "2x16x16"])
    # Every artifact this run emitted must parse under *strict* JSON
    # (json.dump with allow_nan=False upstream; a bare NaN/Infinity here
    # fails CI instead of poisoning the perf-trajectory records).  Only
    # files this run wrote are checked — a stale pre-existing artifact
    # must not fail an unrelated run.
    artifacts = sorted(p for p in emitted if os.path.exists(p))
    if artifacts:
        validate_bench_json(artifacts)
        print(f"[validated strict JSON: {', '.join(artifacts)}]")
    print(f"\n[benchmarks done in {time.time() - t0:.1f}s]")
    return 0


if __name__ == "__main__":
    sys.exit(main())
