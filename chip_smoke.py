"""Smoke run of the system's main paths on TPU.

    python chip_smoke.py [--seed N] [--out DIR]
    python chip_smoke.py --chips 4 [--seed N] [--out DIR]

On one chip it runs, in one process and in order:

1. device     -- the default JAX backend must be TPU; on any other
                 platform the script exits 1 before any other phase.
2. nuts       -- 1024-chain NUTS on Bayesian logistic regression
                 (10,000 points x 100 regressors, the paper's experiment)
                 through ``make_nuts_kernel(backend="pc")``, cold and warm;
                 its posterior mean is checked against hand-written
                 iterative NUTS run on the same chip.
3. exactness  -- a small NUTS program: the pc VM against the unbatched
                 reference interpreter, and the Pallas stack kernels
                 against the XLA stack path; the kernel executable must
                 hold a compiled Mosaic kernel (``tpu_custom_call``).
4. serve      -- smollm-135m at its published widths (random weights)
                 serving 16 open-loop requests through
                 ``GenerationEngine.serve``; every token must equal the
                 per-request greedy oracle's.

With ``--chips 4`` it runs only the lane-sharded path: NUTS over a
4-device lane mesh against the same kernel unsharded, with the sharded
outputs spread over all four devices -- bit for bit on a correlated
Gaussian, and within the phase-2 tolerance on the phase-2 logistic
regression, whose gradient XLA compiles differently per batch size
(see ``sharded_phase``).

Data, weights, prompts and PRNG keys all derive from ``--seed``.  Times
printed are smoke readings, not measurements.  The persistent compile
cache is ``$JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache/``
next to this file.  Any failed check raises, so the script exits
nonzero; only a run whose every check passed prints the last line,
``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro import configs  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.mcmc import iterative, nuts, targets  # noqa: E402
from repro.models import get_model  # noqa: E402
from repro.serve.engine import (  # noqa: E402
    EngineConfig,
    GenerationEngine,
    Request,
)

#: Phase-2 deployment: the paper's logistic regression at full scale.
NUTS_CHAINS = 1024
NUTS_DATA, NUTS_DIM = 10_000, 100
NUTS_SETTINGS = nuts.NutsSettings(
    max_tree_depth=10, num_steps=5, steps_per_leaf=4
)
NUTS_EPS = 0.01
#: Step size for the correlated Gaussian (rho 0.95; narrowest sd 0.16).
GAUSS_EPS = 0.1
#: Posterior means of the two samplers agree within this many Monte Carlo
#: standard errors, per coordinate (the SE comes from the chains' spread).
MEAN_TOL_SE = 5.0
#: Phase 3: pc VM vs reference interpreter, both at HIGHEST matmul
#: precision, differ only by the order of float reductions.
EXACT_TOL = 1e-5


class SmokeFailure(AssertionError):
    """A smoke check did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"  check passed: {what}")


class CompileClock:
    """Seconds JAX spent compiling (cache reads included) and cache hits."""

    def __init__(self):
        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def reading(self) -> tuple[float, int]:
        return self.seconds, self.hits


def timed(fn, *args):
    """(result, seconds) of ``fn(*args)``, waiting for the device."""
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def check_posterior_means(a: dict, b: dict, num_steps: int,
                          what: str) -> float:
    """Check two chain sets' posterior means agree within MEAN_TOL_SE
    Monte Carlo standard errors per coordinate (the SE comes from the
    chains' own spread); return the largest such z."""
    a = np.asarray(a["sum_theta"], np.float64) / num_steps
    b = np.asarray(b["sum_theta"], np.float64) / num_steps
    se = np.sqrt(a.var(0, ddof=1) / len(a) + b.var(0, ddof=1) / len(b))
    z = np.abs(a.mean(0) - b.mean(0)) / se
    print(f"  posterior mean: max |diff| "
          f"{np.abs(a.mean(0) - b.mean(0)).max():.6g}, max z {z.max():.3f}; "
          f"tolerance {MEAN_TOL_SE} SE per coordinate "
          f"(SE {se.min():.3g}..{se.max():.3g})")
    check(bool(np.all(np.isfinite(z)) and z.max() <= MEAN_TOL_SE),
          f"{what} within {MEAN_TOL_SE} standard errors")
    return float(z.max())


def peak_bytes() -> int | None:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def device_phase(cache_dir: str) -> jax.Device:
    devices = jax.devices()
    d0 = devices[0]
    print("[phase 1] device")
    print(f"  platform: {d0.platform}")
    print(f"  device_kind: {d0.device_kind}")
    print(f"  device count: {len(devices)}")
    print(f"  compile cache: {cache_dir}")
    check(d0.platform == "tpu", f"default backend is TPU (got {d0.platform})")
    return d0


def nuts_phase(seed: int, clock: CompileClock, *, chains: int = NUTS_CHAINS,
               num_data: int = NUTS_DATA, dim: int = NUTS_DIM,
               settings: nuts.NutsSettings = NUTS_SETTINGS,
               eps: float = NUTS_EPS) -> dict:
    print(f"[phase 2] NUTS: {chains} chains, logistic regression "
          f"{num_data} x {dim}, {settings}")
    target = targets.logistic_regression(
        num_data=num_data, dim=dim, seed=seed
    )
    args = nuts.initial_state(target, chains, eps=eps, seed=seed)
    kernel = nuts.make_nuts_kernel(target, settings, backend="pc")
    c0 = clock.reading()
    out, cold = timed(kernel, *args)
    c1 = clock.reading()
    out, warm = timed(kernel, *args)
    res = kernel.last_result
    print(f"  cold call: {cold:.3f}s (compile {c1[0] - c0[0]:.3f}s, "
          f"{c1[1] - c0[1]} persistent-cache hits); warm call: {warm:.3f}s")
    print(f"  VM steps: {int(res.steps)}; grad utilization: "
          f"{kernel.utilization['grad']:.4f}; peak bytes: {peak_bytes()}")
    check(bool(res.converged), "every chain halted (converged)")
    check(all(np.isfinite(np.asarray(v)).all() for v in out.values()),
          "every NUTS output is finite")

    ref, ref_s = timed(iterative.run_batched, target, settings, *args)
    print(f"  iterative NUTS on the same chip: {ref_s:.3f}s incl. compile")
    max_z = check_posterior_means(
        out, ref, settings.num_steps,
        "posterior mean agrees with iterative NUTS",
    )
    return {"cold_s": cold, "warm_s": warm, "vm_steps": int(res.steps),
            "grad_utilization": kernel.utilization["grad"], "max_z": max_z}


def exactness_phase(seed: int, *, chains: int = 8) -> dict:
    print("[phase 3] VM exactness: pc vs reference, kernel vs XLA stacks")
    target = targets.isotropic_gaussian(10)
    settings = nuts.NutsSettings(max_tree_depth=4, num_steps=2)
    args = nuts.initial_state(target, chains, eps=0.2, seed=seed)
    with jax.default_matmul_precision("highest"):
        pc = nuts.make_nuts_kernel(target, settings, backend="pc")
        out = jax.device_get(pc(*args))
        ref = nuts.make_nuts_kernel(target, settings, backend="reference")(
            *args
        )
        diff = max(
            float(np.max(np.abs(np.asarray(out[k]) - np.asarray(ref[k]))))
            for k in out
        )
        print(f"  pc vs reference: largest difference {diff:.3g} "
              f"(tolerance {EXACT_TOL} abs + rel)")
        for k in out:
            np.testing.assert_allclose(
                out[k], np.asarray(ref[k]), rtol=EXACT_TOL, atol=EXACT_TOL,
                err_msg=f"pc vs reference, output {k!r}",
            )
        check(True, "pc VM equals the reference interpreter, every chain")

        kern = nuts.make_nuts_kernel(target, settings, backend="pc",
                                     use_kernel=True)
        kout = jax.device_get(kern(*args))
        kdiff = max(
            float(np.max(np.abs(np.asarray(kout[k]) - np.asarray(out[k]))))
            for k in out
        )
        print(f"  use_kernel=True vs False: largest difference {kdiff:.3g} "
              f"(tolerance 0: the kernels only move data)")
        check(all(np.array_equal(kout[k], out[k]) for k in out),
              "Pallas stack kernels are bit-exact with the XLA stack path")
        text = kern.lower(*args).compile().as_text()
        check("tpu_custom_call" in text,
              "the use_kernel executable holds a compiled Mosaic kernel "
              "(tpu_custom_call), not interpret mode")
    return {"pc_vs_reference": diff, "kernel_vs_xla": kdiff}


def serve_phase(seed: int, clock: CompileClock, *, cfg=None,
                lanes: int = 8, max_context: int = 256,
                max_prompt_len: int = 64, max_new_tokens: int = 32,
                num_requests: int = 16, min_prompt_len: int = 8) -> dict:
    cfg = configs.get_config("smollm-135m") if cfg is None else cfg
    cfg = dataclasses.replace(cfg, compute_dtype="float32")
    print(f"[phase 4] serve {cfg.name}: {cfg.num_layers} layers, d_model "
          f"{cfg.d_model}, heads {cfg.num_heads}/{cfg.num_kv_heads}, vocab "
          f"{cfg.vocab_size}; {num_requests} requests over {lanes} lanes")
    rng = np.random.default_rng(seed)
    per_lane = -(-num_requests // lanes)
    requests, t = [], 0.0
    for rid in range(num_requests):
        n = int(rng.integers(min_prompt_len, max_prompt_len + 1))
        prompt = rng.integers(1, cfg.vocab_size, n).astype(np.int32)
        requests.append(Request(rid=rid, prompt=prompt, arrival=t))
        t += float(rng.exponential(0.02))
    # Float32 weights and HIGHEST matmul precision in both the engine and
    # the oracle, so no greedy near-tie can flip between them.
    with jax.default_matmul_precision("highest"):
        model = get_model(cfg)
        params = model.init(jax.random.PRNGKey(seed))
        engine = GenerationEngine(model, params, EngineConfig(
            lanes=lanes, max_context=max_context,
            max_prompt_len=max_prompt_len, max_new_tokens=max_new_tokens,
            requests_per_lane=per_lane, eos_id=0, temperature=0.0,
            backend="pc",
        ))
        c0 = clock.reading()
        t0 = time.perf_counter()
        engine.serve([Request(rid=0, prompt=requests[0].prompt)], seed=seed)
        warmup = time.perf_counter() - t0
        c1 = clock.reading()
        print(f"  warm-up serve (compiles the engine): {warmup:.3f}s "
              f"(compile {c1[0] - c0[0]:.3f}s, {c1[1] - c0[1]} "
              f"persistent-cache hits)")
        comps, stats = engine.serve(requests, seed=seed)
        p50 = float(np.median([c.latency for c in comps]))
        print(f"  served {stats.completions} requests, "
              f"{stats.generated_tokens} tokens in {stats.wall_time:.3f}s: "
              f"{stats.generated_tokens / stats.wall_time:.1f} tokens/s, "
              f"p50 latency {p50:.3f}s, "
              f"{stats.segments} segments; peak bytes: {peak_bytes()}")

        # Oracle: request i is lane i // per_lane, queue slot i % per_lane.
        prompts = np.zeros((lanes, per_lane, max_prompt_len), np.int32)
        plens = np.zeros((lanes, per_lane), np.int32)
        for r in requests:
            lane, slot = divmod(r.rid, per_lane)
            prompts[lane, slot, : len(r.prompt)] = r.prompt
            plens[lane, slot] = len(r.prompt)
        oracle = engine.reference_generate(prompts, plens)
    check([c.rid for c in comps] == list(range(num_requests))
          and all(c.status == "ok" for c in comps),
          f"all {num_requests} requests ended ok")
    mismatched = []
    for c in comps:
        lane, slot = divmod(c.rid, per_lane)
        want = oracle["tokens"][lane, slot, : oracle["lengths"][lane, slot]]
        if not np.array_equal(c.tokens, want):
            mismatched.append(c.rid)
    check(not mismatched,
          "every request's tokens (and token count) equal the per-request "
          f"greedy oracle's (mismatched: {mismatched})")
    return {"tokens": stats.generated_tokens, "wall_s": stats.wall_time,
            "p50_latency_s": p50, "warmup_s": warmup}


def _sharded_vs_unsharded(target, chips, settings, args):
    one = nuts.make_nuts_kernel(target, settings, backend="pc")
    many = nuts.make_nuts_kernel(target, settings, backend="pc", mesh=chips)
    ref, t_one = timed(one, *args)
    out, t_many = timed(many, *args)
    print(f"  {target.name}: unsharded {t_one:.3f}s, sharded {t_many:.3f}s "
          f"(cold, compile included)")
    check(bool(one.last_result.converged and many.last_result.converged),
          "every chain halted in both runs")
    spread = {k: len(v.sharding.device_set) for k, v in out.items()}
    print(f"  devices holding each sharded output: {spread}")
    check(all(n == chips for n in spread.values()),
          f"sharded outputs are spread over {chips} devices")
    out = jax.device_get(out)
    ref = jax.device_get(ref)
    same = np.all([np.all(out[k] == ref[k], axis=1) for k in ref], axis=0)
    diff = max(float(np.max(np.abs(out[k] - ref[k]))) for k in ref)
    print(f"  chains bit-identical to unsharded: {int(same.sum())}/"
          f"{len(same)}; largest difference {diff:.3g}")
    return out, ref, bool(same.all())


def sharded_phase(seed: int, chips: int, *, chains: int = NUTS_CHAINS,
                  num_data: int = NUTS_DATA, dim: int = NUTS_DIM,
                  settings: nuts.NutsSettings = NUTS_SETTINGS,
                  eps: float = NUTS_EPS) -> dict:
    """NUTS over a ``chips``-device lane mesh against the same kernel
    unsharded.

    The VM's control is exact under sharding (every dispatch decision is
    an integer reduction), so a target whose per-lane arithmetic XLA
    compiles the same at any per-device batch gives bit-identical chains:
    the correlated Gaussian (elementwise, plus reductions within a lane's
    own row).  The phase-2 logistic regression does not: on TPU XLA tiles
    its 10,000-point gradient contraction by the batch size, so the last
    bits of each gradient depend on the chains per device and the chains
    diverge.  That pair is held to the statistical agreement of phase 2.
    """
    print(f"[phase 2, sharded] NUTS: {chains} chains over a {chips}-device "
          f"lane mesh vs unsharded")
    check(len(jax.devices()) >= chips, f"at least {chips} devices visible")
    exact = targets.correlated_gaussian(dim)
    _, _, identical = _sharded_vs_unsharded(
        exact, chips, settings,
        nuts.initial_state(exact, chains, eps=GAUSS_EPS, seed=seed),
    )
    check(identical, f"sharded NUTS on {exact.name} is bit-exact with "
                     "unsharded")
    logreg = targets.logistic_regression(
        num_data=num_data, dim=dim, seed=seed
    )
    out, ref, _ = _sharded_vs_unsharded(
        logreg, chips, settings,
        nuts.initial_state(logreg, chains, eps=eps, seed=seed),
    )
    max_z = check_posterior_means(
        out, ref, settings.num_steps,
        f"sharded NUTS on {logreg.name} agrees with unsharded",
    )
    return {"max_z": max_z}


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the lane-sharded NUTS comparison")
    ap.add_argument("--out", default=None,
                    help="directory for chip_smoke.json (phase readings)")
    args = ap.parse_args(argv)

    cache_dir = compile_cache.enable(ROOT)
    clock = CompileClock()
    d0 = device_phase(cache_dir)
    readings = {}
    if args.chips == 1:
        readings["nuts"] = nuts_phase(args.seed, clock)
        readings["exactness"] = exactness_phase(args.seed)
        readings["serve"] = serve_phase(args.seed, clock)
    else:
        readings["sharded"] = sharded_phase(args.seed, args.chips)
    compile_s, hits = clock.reading()
    print(f"total compile: {compile_s:.3f}s, {hits} persistent-cache hits")
    verdict = {
        "ok": True,
        "device": {"platform": d0.platform, "kind": d0.device_kind,
                   "count": len(jax.devices())},
    }
    if args.out:
        out = Path(args.out)
        out.mkdir(parents=True, exist_ok=True)
        (out / "chip_smoke.json").write_text(json.dumps(
            {**verdict, "compile_s": compile_s, "cache_hits": hits,
             "readings": readings}, indent=2, default=float))
    print(json.dumps(verdict), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
