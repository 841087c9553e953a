import os
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=512"
).strip()
# The lines above MUST run before any jax import: jax locks the device
# count at first init, and the dry-run needs 512 placeholder host devices
# to build the production meshes.  (Only this entry point does this —
# tests and benches see the single real CPU device.)

"""Multi-pod dry-run: prove the distribution config is coherent.

For every (architecture x applicable input shape) cell and both production
meshes (single-pod 16x16, multi-pod 2x16x16), this driver:

  1. builds the jittable step (train_step / prefill_step / serve_step),
  2. ``.lower()``s it with ShapeDtypeStruct stand-ins (no allocation) and
     explicit in/out shardings from ``repro.launch.sharding``,
  3. ``.compile()``s it — sharding mismatches, unsupported collectives and
     compile-time OOMs surface here as hard failures,
  4. records ``memory_analysis()`` (fits-per-device proof),
     ``cost_analysis()`` (FLOPs / bytes) and the collective schedule
     parsed from the compiled HLO (op kind -> bytes moved per device),
     into a JSON artifact consumed by the roofline report.

Usage:
  python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k
  python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k --multi-pod
  python -m repro.launch.dryrun --all --out-dir benchmarks/artifacts
"""
import argparse
import json
import re
import sys
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.configs.base import SHAPES, applicable_shapes
from repro.launch import hlo_cost
from repro.launch import sharding as sh
from repro.launch.mesh import make_production_mesh, num_chips
from repro.models import get_model
from repro.serve.steps import decode_cache_window, make_prefill_step, \
    make_serve_step
from repro.train import optimizer as opt_lib
from repro.train import train_step as ts

# ---------------------------------------------------------------------------
# TPU v5e hardware model (roofline constants)
# ---------------------------------------------------------------------------
PEAK_FLOPS = 197e12  # bf16 FLOP/s per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)

# effective bytes-on-the-wire multiplier per collective kind (ring algos)
_WIRE_FACTOR = {
    "all-reduce": 2.0,  # reduce-scatter + all-gather
    "all-gather": 1.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def parse_collectives(hlo_text: str) -> dict[str, dict[str, float]]:
    """Sum result bytes of every collective op in the (post-SPMD, hence
    per-device-shaped) HLO.  Returns kind -> {count, bytes}."""
    out: dict[str, dict[str, float]] = {
        k: {"count": 0, "bytes": 0.0} for k in _COLLECTIVES
    }
    for line in hlo_text.splitlines():
        ls = line.strip()
        if "=" not in ls:
            continue
        rhs = ls.split("=", 1)[1].lstrip()
        # result type(s) precede the op name:  f32[8,128]{1,0} all-reduce(
        m = re.match(r"^(\(?[\w\[\],{}\s/]*?)\s*(all-reduce|all-gather|"
                     r"reduce-scatter|all-to-all|collective-permute)"
                     r"(-start|-done)?\(", rhs)
        if not m:
            continue
        kind = m.group(2)
        if m.group(3) == "-done":
            continue  # counted at -start
        restype = m.group(1)
        nbytes = sum(
            _shape_bytes(dt, dims) for dt, dims in _SHAPE_RE.findall(restype)
        )
        out[kind]["count"] += 1
        out[kind]["bytes"] += nbytes
    return {k: v for k, v in out.items() if v["count"]}


def collective_seconds(coll: dict[str, dict[str, float]]) -> float:
    return sum(
        v["bytes"] * _WIRE_FACTOR[k] / ICI_BW for k, v in coll.items()
    )


# ---------------------------------------------------------------------------
# Case construction
# ---------------------------------------------------------------------------


def default_microbatches(arch: str, shape_name: str, mesh) -> int:
    """Gradient-accumulation factor targeting ~8k local tokens per
    microbatch (the production memory lever; recorded per cell)."""
    shape = SHAPES[shape_name]
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp = sizes.get("data", 1) * sizes.get("pod", 1)
    local_tokens = shape.global_batch * shape.seq_len // dp
    local_seqs = max(1, shape.global_batch // dp)
    mb = max(1, local_tokens // 8192)
    return min(mb, local_seqs)  # cannot split below 1 sequence


def build_case(arch: str, shape_name: str, mesh, *, unroll: bool = True,
               remat: str = "full", compress_grads: bool = False,
               use_flash: bool = False, microbatches: int = 1,
               cfg_overrides: dict | None = None):
    """Returns (name, fn, arg_specs, in_shardings)."""
    import dataclasses as _dc

    cfg = configs.get_config(arch)
    if cfg_overrides:
        cfg = _dc.replace(cfg, **cfg_overrides)
    shape = SHAPES[shape_name]
    model = get_model(cfg, use_flash=use_flash)
    model.unroll = unroll
    model.axis_rules = {
        "batch": ("pod", "data") if "pod" in mesh.axis_names else ("data",),
        "tp": "model",
        "ep": "model",
        "sizes": dict(zip(mesh.axis_names, mesh.devices.shape)),
        "mesh": mesh,
    }

    params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pshard = sh.param_shardings(params_shape, mesh)

    if shape.kind == "train":
        tcfg = ts.TrainConfig(
            microbatches=microbatches, remat=remat,
            opt=opt_lib.OptimizerConfig(compress_grads=compress_grads),
        )
        step = ts.make_train_step(model, tcfg)
        opt_shape = jax.eval_shape(
            lambda p: opt_lib.init_opt_state(p, tcfg.opt), params_shape
        )
        oshard = sh.opt_state_shardings(opt_shape, params_shape, mesh)
        batch_shape = model.input_specs(shape)
        bshard = sh.batch_shardings(batch_shape, mesh)
        in_shardings = (pshard, oshard, bshard)
        out_shardings = (pshard, oshard, sh.replicated(mesh))
        args = (params_shape, opt_shape, batch_shape)
        return "train_step", step, args, in_shardings, out_shardings

    if shape.kind == "prefill":
        step = make_prefill_step(model)
        batch_shape = model.input_specs(shape)
        bshard = sh.batch_shardings(batch_shape, mesh)
        in_shardings = (pshard, bshard)
        # logits replicated-batch-sharded output
        out_shardings = None
        args = (params_shape, batch_shape)
        return "prefill_step", step, args, in_shardings, out_shardings

    # decode
    window = decode_cache_window(cfg, shape)
    b = shape.global_batch
    serve = make_serve_step(model)
    cache_shape = jax.eval_shape(lambda: model.init_cache(b, window))
    cshard = sh.cache_shardings(cache_shape, b, mesh)
    tok = jax.ShapeDtypeStruct((b,), jnp.int32)
    pos = jax.ShapeDtypeStruct((b,), jnp.int32)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    bshard = sh.batch_shardings({"t": tok, "p": pos}, mesh)
    in_shardings = (pshard, cshard, bshard["t"], bshard["p"],
                    sh.replicated(mesh))
    out_shardings = (bshard["t"], cshard)
    args = (params_shape, cache_shape, tok, pos, key)
    return "serve_step", serve, args, in_shardings, out_shardings


def model_flops_per_chip(arch: str, shape_name: str, chips: int) -> float:
    """Useful model FLOPs per chip per step: 6·N_active·tokens for train
    (fwd+bwd), 2·N_active·tokens for inference steps."""
    cfg = configs.get_config(arch)
    shape = SHAPES[shape_name]
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        mult = 6.0
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        mult = 2.0
    else:  # decode: one token per sequence per step
        tokens = shape.global_batch
        mult = 2.0
    return mult * n * tokens / chips


def bytes_floor_per_chip(arch: str, shape_name: str, chips: int) -> float:
    """Lower bound on HBM traffic per chip per step.

    train:   3 bf16 weight streams (fwd, bwd-dgrad, bwd-wgrad) + AdamW
             state read/write (f32 mu, nu, params);
    prefill: one bf16 weight stream;
    decode:  one bf16 weight stream + one pass over the KV/state cache.
    """
    cfg = configs.get_config(arch)
    shape = SHAPES[shape_name]
    n = cfg.active_param_count()
    if shape.kind == "train":
        return (3 * 2 * n + 3 * 2 * 4 * n) / chips
    if shape.kind == "prefill":
        return 2 * n / chips
    # decode: cache bytes from the abstract cache pytree
    from repro.serve.steps import decode_cache_window

    model = get_model(cfg)
    window = decode_cache_window(cfg, shape)
    cache_shape = jax.eval_shape(
        lambda: model.init_cache(shape.global_batch, window)
    )
    cache_bytes = sum(
        int(np.prod(l.shape)) * l.dtype.itemsize
        for l in jax.tree_util.tree_leaves(cache_shape)
    )
    return (2 * n + cache_bytes) / chips


def _lower_compile(arch, shape_name, mesh, **kw):
    name, fn, args, in_sh, out_sh = build_case(arch, shape_name, mesh, **kw)
    # donate params/opt-state (train) or the cache (decode): the compiled
    # step aliases them in place, so memory_analysis reflects production.
    donate = (0, 1) if name == "train_step" else (
        (1,) if name == "serve_step" else ()
    )
    t0 = time.time()
    with mesh:
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=donate)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
    return name, compiled, t_lower, t_compile


def attn_flash_io_bytes(arch: str, shape_name: str, chips: int,
                        cfg_overrides: dict | None = None) -> float:
    """Per-chip HBM traffic of attention if the Pallas flash kernel ran
    instead of XLA-blocked attention: q,k,v read + o written per
    application (x3 passes for training: fwd, bwd reads + dq/dk/dv)."""
    import dataclasses as _dc

    cfg = configs.get_config(arch)
    if cfg_overrides:
        cfg = _dc.replace(cfg, **cfg_overrides)
    shape = SHAPES[shape_name]
    if cfg.family == "ssm":
        return 0.0
    if cfg.family == "hybrid":
        n_apps = cfg.num_layers // cfg.shared_attn_every
    else:
        n_apps = cfg.num_layers
    dh = cfg.resolved_head_dim
    if shape.kind == "decode":
        tokens = shape.global_batch  # one new token each; cache bytes are
        # already part of the floor — flash-decode reads the cache once.
        passes = 1
    else:
        tokens = shape.global_batch * shape.seq_len
        passes = 3 if shape.kind == "train" else 1
    io = tokens * dh * (2 * cfg.num_heads + 2 * cfg.num_kv_heads) * 2
    return passes * n_apps * io / chips


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             unroll: bool = False, remat: str = "full",
             compress_grads: bool = False, use_flash: bool = False,
             cfg_overrides: dict | None = None,
             microbatches: int | None = None,
             mesh_shape: tuple | None = None,
             verbose: bool = True) -> dict[str, Any]:
    """Lower+compile the production configuration (lax.scan layer
    stacks, gradient accumulation, remat) and derive the roofline terms.

    FLOPs/bytes/collectives come from the loop-aware HLO parser
    (repro.launch.hlo_cost), which multiplies while-loop bodies by their
    recovered trip counts — XLA's own cost_analysis counts each loop body
    once.  ``unroll=True`` instead unrolls every layer into the HLO and
    uses XLA's analysis directly (slow; the validation path).
    """
    if mesh_shape is not None:
        # logical remesh over the same chips (e.g. (32, 8) when an arch's
        # head count does not divide 16) — a per-arch deployment choice;
        # the canonical 16x16 dry-run proof is separate.
        axes = (("pod", "data", "model") if len(mesh_shape) == 3
                else ("data", "model"))
        mesh = jax.make_mesh(
            tuple(mesh_shape), axes,
            axis_types=(jax.sharding.AxisType.Auto,) * len(mesh_shape),
        )
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    chips = num_chips(mesh)
    is_train = SHAPES[shape_name].kind == "train"
    if microbatches is None:
        microbatches = (
            default_microbatches(arch, shape_name, mesh) if is_train else 1
        )
    name, compiled, t_lower, t_compile = _lower_compile(
        arch, shape_name, mesh, unroll=unroll, remat=remat,
        compress_grads=compress_grads, use_flash=use_flash,
        microbatches=microbatches, cfg_overrides=cfg_overrides,
    )
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    if unroll:
        # every layer explicit in the HLO: use XLA's own cost analysis
        cost = compiled.cost_analysis()
        flops = float(cost.get("flops", 0.0))
        bytes_accessed = float(cost.get("bytes accessed", 0.0))
        coll = parse_collectives(hlo)
        scope_bytes: dict = {}
    else:
        # production scan config: loop-aware static accounting
        lac = hlo_cost.analyze(hlo)
        flops = lac.flops
        bytes_accessed = lac.bytes_accessed
        coll = lac.collectives
        scope_bytes = lac.scope_bytes
    result = {
        "arch": arch,
        "shape": shape_name,
        "step": name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "unroll": unroll,
        "remat": remat,
        "microbatches": microbatches,
        # memory (per device)
        "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
        "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
        "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
        "peak_bytes": int(
            getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "temp_size_in_bytes", 0)
        ),
        # cost (per device, post-partition)
        "hlo_flops": flops,
        "hlo_bytes": bytes_accessed,
        "model_flops": model_flops_per_chip(arch, shape_name, chips),
        "collectives": coll,
        "collective_bytes": sum(v["bytes"] for v in coll.values()),
        # roofline terms (seconds)
        "t_compute": flops / PEAK_FLOPS,
        "t_memory": bytes_accessed / HBM_BW,
        "t_collective": collective_seconds(coll),
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
    }
    terms = {
        "compute": result["t_compute"],
        "memory": result["t_memory"],
        "collective": result["t_collective"],
    }
    result["bottleneck"] = max(terms, key=terms.get)
    result["useful_flops_ratio"] = (
        result["model_flops"] / flops if flops else 0.0
    )
    # roofline fraction: ideal step time (the larger of the useful-FLOPs
    # bound and the bytes-floor bound) over the dominant achieved term
    floor = bytes_floor_per_chip(arch, shape_name, chips)
    result["bytes_floor"] = floor
    t_bound = max(terms.values())
    t_ideal = max(result["model_flops"] / PEAK_FLOPS, floor / HBM_BW)
    result["t_ideal"] = t_ideal
    result["roofline_fraction"] = t_ideal / t_bound if t_bound else 0.0
    # ---- Pallas-flash-kernel modeling (validated in interpret mode; the
    # kernel keeps score blocks in VMEM, so the attn_core scope's HBM
    # traffic collapses to the q/k/v/o streams) ----
    result["scope_bytes"] = scope_bytes
    attn_scope = scope_bytes.get("attn_core", 0.0)
    if attn_scope:
        flash_io = attn_flash_io_bytes(arch, shape_name, chips,
                                       cfg_overrides)
        bytes_flash = bytes_accessed - attn_scope + flash_io
        t_mem_flash = bytes_flash / HBM_BW
        result["t_memory_flash"] = t_mem_flash
        terms_f = dict(terms, memory=t_mem_flash)
        tb_f = max(terms_f.values())
        result["bottleneck_flash"] = max(terms_f, key=terms_f.get)
        result["roofline_fraction_flash"] = (
            t_ideal / tb_f if tb_f else 0.0
        )
    if verbose:
        print(json.dumps(result, indent=2, default=float))
    return result


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def all_cells() -> list[tuple[str, str]]:
    cells = []
    for arch in configs.list_archs():
        for shape in applicable_shapes(configs.get_config(arch)):
            cells.append((arch, shape.name))
    return cells


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=configs.list_archs())
    ap.add_argument("--shape", choices=sorted(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="sweep every applicable cell on this mesh")
    ap.add_argument("--unroll", action="store_true",
                    help="unroll layer stacks (slow compile; used to "
                         "validate the loop-aware accounting)")
    ap.add_argument("--remat", default="full",
                    choices=["none", "full", "dots"])
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--use-flash", action="store_true")
    ap.add_argument("--out", help="write JSON result(s) to this path")
    args = ap.parse_args(argv)

    unroll = args.unroll
    results = []
    if args.all:
        for arch, shape in all_cells():
            print(f"=== {arch} x {shape} ({'2x16x16' if args.multi_pod else '16x16'}) ===",
                  flush=True)
            results.append(run_cell(
                arch, shape, multi_pod=args.multi_pod, unroll=unroll,
                remat=args.remat, compress_grads=args.compress_grads,
                use_flash=args.use_flash,
            ))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required (or --all)")
        results.append(run_cell(
            args.arch, args.shape, multi_pod=args.multi_pod, unroll=unroll,
            remat=args.remat, compress_grads=args.compress_grads,
            use_flash=args.use_flash,
        ))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=2, default=float)
    return 0


if __name__ == "__main__":
    sys.exit(main())
