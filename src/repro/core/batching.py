"""Decorator-first, pytree-native autobatching API (the ``vmap``-like surface).

This is the public entry point of the autobatching core.  Where the legacy
``api.autobatch(program, batch_size)`` interface consumed a hand-built IR
program and a dict of qualified string names, this module exposes the paper's
"general program transformation" the way users expect to hold it: a decorator
over restricted Python (or over a :class:`~repro.core.frontend.FunctionBuilder`
program) returning a callable over **positional pytree arguments**::

    from repro.core.batching import autobatch, Batched, Shared
    from repro.core.frontend import I32

    @autobatch(in_specs=(Batched(I32),), out_spec=I32, backend="pc")
    def fib(n):
        if n < 2:
            return n
        return fib(n - 1) + fib(n - 2)

    fib(np.arange(8, dtype=np.int32))        # -> [8] int32 array

Argument model (the ``in_axes`` analog)
---------------------------------------
``Batched(spec)``  — per-member state: the call-time value carries a leading
                     batch axis on every leaf (``vmap``'s ``in_axes=0``).
``Shared(spec)``   — broadcast constants (step sizes, target parameters):
                     the call-time value has *no* batch axis and is shared by
                     every member (``vmap``'s ``in_axes=None``).

Specs are pytrees of ``jax.ShapeDtypeStruct`` (arrays and dtypes are
accepted and normalized).  A multi-leaf pytree argument binds its leaves to
consecutive IR parameters in flatten order; the binding is recorded on the
program's main :class:`ir.Function` as an :class:`ir.Interface` so the
calling convention travels with the IR.

Execution cache
---------------
Tracing (frontend -> IR) happens once per decorated function; the pc
backend's stack-explicit lowering happens once per *program*; per-batch-size
executors and per-aval compiled artifacts are memoized under a
``(backend, batch_size, schedule, fuse, verify, dce, on_fault,
detect_nonfinite, lane_step_budget, compact_every, trace, mesh,
pgo digest, input avals)`` key.  ``cache_info()`` exposes the
counters so callers (and tests) can prove that a repeat call at the same
avals performs no re-trace, no re-lower, and no re-compile, and that a call
at a *new* batch size reuses the lowering.

AOT
---
``fn.lower(*args)`` returns an :class:`AotLowered` handle with
``as_text()`` / ``compile()`` / ``cost_analysis()`` — the replacement for the
legacy ``BatchedProgram.lower_aot``.
"""
from __future__ import annotations

import functools
import inspect
import os
from dataclasses import dataclass
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import (
    analysis,
    ast_frontend,
    frontend,
    ir,
    local_static,
    lowering,
    passes,
    pc_vm,
    reference,
)

__all__ = [
    "Batched",
    "Shared",
    "AutobatchedFunction",
    "AotLowered",
    "Stepper",
    "autobatch",
    "DEFAULT_NAMESPACE",
]

BACKENDS = ("pc", "local", "local_eager", "reference")

#: Fallback stack depth when ``max_depth=None`` and the program is
#: recursive: an input-dependent call depth has no static bound, so the
#: historical default applies (the overflow message then names the cycle).
DEFAULT_MAX_DEPTH = 32

#: The default unified frontend namespace.  ``@autobatch`` registrations land
#: here unless an explicit ``registry=`` is passed, so decorated functions in
#: one module can call decorated (or builder-registered) functions in another.
DEFAULT_NAMESPACE = ast_frontend.Namespace()


# --------------------------------------------------------------------------
# Argument annotations
# --------------------------------------------------------------------------


class Batched:
    """Per-member argument: call-time leaves carry a leading batch axis."""

    shared = False

    def __init__(self, spec: Any):
        self.spec = spec

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return f"Batched({self.spec!r})"


class Shared:
    """Broadcast argument: one value shared by every batch member."""

    shared = True

    def __init__(self, spec: Any):
        self.spec = spec

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return f"Shared({self.spec!r})"


def _as_profile(pgo: Any):
    """Normalize the ``pgo=`` knob: None, a ``BlockProfile``, or a path to
    a profile JSON saved by ``BlockProfile.save`` (loaded here)."""
    if pgo is None:
        return None
    if isinstance(pgo, (str, os.PathLike)):
        from repro.obs.blockprof import BlockProfile

        return BlockProfile.load(pgo)
    if hasattr(pgo, "dispatches") and hasattr(pgo, "digest"):
        return pgo
    raise TypeError(
        "pgo= expects a repro.obs.blockprof.BlockProfile (or a path to "
        f"one saved as JSON), got {type(pgo).__name__}"
    )


def _as_spec(x: Any) -> jax.ShapeDtypeStruct:
    if isinstance(x, jax.ShapeDtypeStruct):
        return x
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return jax.ShapeDtypeStruct(tuple(x.shape), jnp.dtype(x.dtype))
    return jax.ShapeDtypeStruct((), jnp.dtype(x))


def _specs_eq(a: jax.ShapeDtypeStruct, b: jax.ShapeDtypeStruct) -> bool:
    return tuple(a.shape) == tuple(b.shape) and a.dtype == b.dtype


def _flatten_spec(entry: Any) -> tuple[list[jax.ShapeDtypeStruct], Any, bool]:
    """Normalize one ``in_specs`` entry -> (leaf specs, treedef, shared)."""
    wrap = entry if isinstance(entry, (Batched, Shared)) else Batched(entry)
    leaves, treedef = jax.tree_util.tree_flatten(wrap.spec)
    if not leaves:
        raise TypeError(f"argument spec {entry!r} has no leaves")
    return [_as_spec(l) for l in leaves], treedef, wrap.shared


# --------------------------------------------------------------------------
# Backend executors (one per (backend, batch_size); own the compiled state)
# --------------------------------------------------------------------------


def _raise_if_overflowed(
    flags, batch_size: int, max_depth: int, hint: str = ""
) -> None:
    """Shared overflow gate: silently-corrupted members (dropped
    out-of-range pushes) must never escape the pytree API.

    ``hint`` carries the static stack-depth analysis' guidance (the
    inferred bound, or the recursive cycle that defeats it).  The raised
    :class:`pc_vm.StackOverflow` carries the per-lane evidence —
    ``exc.depth_exceeded`` (the ``[batch]`` bool mask) and ``exc.lanes``
    (the offending lane indices) — so callers can report *which* members
    died.
    """
    if flags.any():
        flags = np.asarray(flags)
        lanes = np.flatnonzero(flags)
        shown = ", ".join(str(i) for i in lanes[:8])
        if len(lanes) > 8:
            shown += ", ..."
        raise pc_vm.StackOverflow(
            f"pc/variable stack overflow: {len(lanes)} of "
            f"{batch_size} batch members exceeded max_depth={max_depth} "
            f"(lanes {shown}); their results would be invalid "
            "(out-of-range pushes are dropped). "
            + (hint or "Pass a larger max_depth= to autobatch()."),
            depth_exceeded=flags,
            lanes=lanes,
        )


def _raise_if_faulted(codes, batch_size: int) -> None:
    """Shared gate for NONFINITE/WATCHDOG faults under ``on_fault="raise"``:
    the batch is aborted with the per-lane evidence on the exception."""
    codes = np.asarray(codes)
    bad = codes >= pc_vm.FAULT_NONFINITE
    if bad.any():
        lanes = np.flatnonzero(bad)
        kinds = sorted({pc_vm.FAULT_NAMES[int(codes[i])] for i in lanes})
        shown = ", ".join(str(i) for i in lanes[:8])
        if len(lanes) > 8:
            shown += ", ..."
        raise pc_vm.LaneFault(
            f"lane fault ({'/'.join(kinds)}): {len(lanes)} of {batch_size} "
            f"batch members faulted (lanes {shown}); their results would "
            "be invalid. Pass on_fault='quarantine' to autobatch() to "
            "contain faults per lane instead of aborting the batch.",
            fault_codes=codes,
        )


class _PcExecutor:
    def __init__(self, lowered: ir.LoweredProgram, main: str,
                 config: pc_vm.VMConfig, overflow_hint: str = ""):
        self.main = main
        self.batch_size = config.batch_size
        self.overflow_hint = overflow_hint
        self.vm = pc_vm.ProgramCounterVM(lowered, config)
        self.last_result: Optional[pc_vm.VMResult] = None

    def _qualify(self, inputs: dict[str, Any]) -> dict[str, Any]:
        return {ir.qualify(self.main, k): v for k, v in inputs.items()}

    def run(self, inputs: dict[str, Any]) -> dict[str, Any]:
        res = self.vm.run(self._qualify(inputs))
        self.last_result = res
        if self.vm.config.on_fault == "raise":
            # Batch-fatal policy (the historical default): a deliberate
            # device sync before results escape the pytree API.  Under
            # "quarantine" nothing raises — faulted lanes are flagged in
            # last_result.fault_code and healthy lanes stay exact.
            if res.depth_exceeded is not None:
                _raise_if_overflowed(
                    jax.device_get(res.depth_exceeded),
                    self.batch_size, self.vm.config.max_depth,
                    self.overflow_hint,
                )
            cfg = self.vm.config
            if res.fault_code is not None and (
                cfg.detect_nonfinite or cfg.lane_step_budget is not None
            ):
                _raise_if_faulted(
                    jax.device_get(res.fault_code), self.batch_size
                )
        return {k.split("/", 1)[1]: v for k, v in res.outputs.items()}

    def lower(self, inputs: dict[str, Any]):
        return self.vm.lower(self._qualify(inputs))

    @property
    def tag_stats(self) -> dict[str, tuple[int, int]]:
        if self.last_result is None:
            return {}
        return dict(self.last_result.tag_stats)


class _LocalExecutor:
    def __init__(self, program: ir.Program, batch_size: int, jit_blocks: bool):
        self.batch_size = batch_size
        self.batcher = local_static.LocalStaticBatcher(
            program, batch_size, jit_blocks=jit_blocks
        )
        self._ran = False
        self.last_result = None

    def run(self, inputs: dict[str, Any]) -> dict[str, Any]:
        # Per-run counters, matching the pc executor's last_result semantics
        # (LocalStaticBatcher accumulates across runs by itself).
        self.batcher.stats = local_static.LocalStats()
        out = self.batcher.run(inputs)
        self._ran = True
        return out

    @property
    def tag_stats(self) -> dict[str, tuple[int, int]]:
        if not self._ran:
            return {}
        st = self.batcher.stats
        return {
            tag: (st.tag_execs.get(tag, 0), st.tag_active.get(tag, 0))
            for tag in st.tag_execs
        }


class _ReferenceExecutor:
    def __init__(self, program: ir.Program, batch_size: int):
        self.program = program
        self.batch_size = batch_size
        self.last_result = None

    def run(self, inputs: dict[str, Any]) -> dict[str, Any]:
        return reference.run_reference_batch(self.program, inputs)

    @property
    def tag_stats(self) -> dict[str, tuple[int, int]]:
        return {}


# --------------------------------------------------------------------------
# AOT handle
# --------------------------------------------------------------------------


class AotLowered:
    """Handle over an AOT-lowered batched computation (pc backend).

    Replaces the legacy ``BatchedProgram.lower_aot``: supports ``as_text()``
    for StableHLO inspection, ``compile()`` for ahead-of-time compilation,
    and ``cost_analysis()`` (flops/bytes estimates from the compiled
    executable when available, falling back to the lowering).
    """

    def __init__(self, lowered):
        self._lowered = lowered
        self._compiled = None

    def as_text(self) -> str:
        return self._lowered.as_text()

    def compile(self):
        if self._compiled is None:
            self._compiled = self._lowered.compile()
        return self._compiled

    def cost_analysis(self) -> dict[str, float]:
        try:
            cost = self.compile().cost_analysis()
        except Exception:
            cost = self._lowered.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        return dict(cost or {})


# --------------------------------------------------------------------------
# Segmented execution handle
# --------------------------------------------------------------------------


class Stepper:
    """Resumable, state-in/state-out execution of an autobatched function.

    Produced by :meth:`AutobatchedFunction.stepper`; pc backend only.  A
    stepper decouples *holding the VM state* from *advancing it*: the
    caller owns an opaque snapshot pytree and threads it through
    ``step()`` segments, which lets a host loop retire finished lanes and
    refill them with new work between segments (continuous batching — see
    ``repro/serve/engine.py``)::

        st = fn.stepper(*args)          # cache-keyed like fn.lower()
        state = st.init()
        while not st.done(state):
            state = st.step(state, 64)  # <= 64 VM dispatches
        out = st.result(state)          # == fn(*args), bit-exactly

    Snapshots are donated: ``step``, ``inject`` and ``park`` consume the
    incoming snapshot — do not reuse a snapshot after passing it in.
    Chaining segments of any sizes is bit-exact with the single-shot call
    for every schedule x fuse x mesh combination (property-tested in
    ``tests/test_core_property.py``).
    """

    def __init__(self, fn: "AutobatchedFunction", inputs: dict, z: int):
        self._fn = fn
        self._ex = fn._executor(z)
        self._inputs = inputs
        self.batch_size = z

    @property
    def vm(self) -> pc_vm.ProgramCounterVM:
        """The underlying VM (shared with plain calls at this batch size)."""
        return self._ex.vm

    def init(self, *args) -> dict:
        """A fresh initial snapshot.

        With no arguments, uses the values ``stepper(...)`` was created
        with; with arguments, re-binds new values (same avals).
        """
        inputs = self._inputs
        if args:
            inputs, z = self._fn._bind(args)
            if z != self.batch_size:
                raise TypeError(
                    f"stepper.init: batch size {z} != {self.batch_size}"
                )
        return self.vm.start(self._ex._qualify(inputs))

    def step(self, state: dict, num_steps: int) -> dict:
        """Advance by at most ``num_steps`` VM loop iterations."""
        return self.vm.run_segment(state, num_steps)

    def lane_done(self, state: dict) -> jax.Array:
        """``[batch]`` bool: which lanes have halted."""
        return self.vm.lane_done(state)

    def fault_code(self, state: dict) -> jax.Array:
        """``[batch]`` i32 per-lane fault codes (``pc_vm.FAULT_NAMES``)."""
        return self.vm.lane_fault(state)

    def lane_faulted(self, state: dict) -> jax.Array:
        """``[batch]`` bool: which lanes have faulted (overflow /
        non-finite write / watchdog).  Faulted lanes never advance again
        under ``on_fault="quarantine"``; ``inject`` resets them."""
        return self.vm.lane_faulted(state)

    def done(self, state: dict) -> bool:
        """True once the VM cannot advance this snapshot any further
        (device sync): every lane has halted or faulted, or the
        ``max_steps`` budget is exhausted — exactly when a single-shot
        call would return, so the ``while not st.done(state)`` drive loop
        terminates whenever ``fn(*args)`` would (check ``lane_done`` /
        ``lane_faulted`` to tell the cases apart).
        """
        terminal = jnp.logical_or(
            self.vm.lane_done(state), self.vm.lane_faulted(state)
        )
        if bool(jax.device_get(jnp.all(terminal))):
            return True
        cfg = self.vm.config
        if cfg.on_fault == "raise" and (
            cfg.detect_nonfinite or cfg.lane_step_budget is not None
        ):
            # Fail-fast policy: the VM loop halts the whole batch at the
            # first detector fault, so no lane will ever advance again —
            # the snapshot is done (result() will raise LaneFault).
            codes = jax.device_get(self.fault_code(state))
            if bool((codes >= pc_vm.FAULT_NONFINITE).any()):
                return True
        return self.steps(state) >= self.vm.config.max_steps

    def steps(self, state: dict) -> int:
        """Total VM loop iterations accumulated in this snapshot."""
        return int(jax.device_get(state["steps"]))

    def trace(self, state: dict):
        """Drain the dispatch trace from a snapshot (device sync).

        Returns a :class:`repro.obs.trace.DispatchTrace` covering every
        dispatch recorded so far (all segments — the ring is part of the
        carried snapshot), or ``None`` when the function was built
        without ``trace=``.  Non-destructive: a later drain sees the
        same events plus any new ones, on the same global step axis.
        """
        return self.vm.get_trace(state)

    def park(self, state: dict, mask) -> dict:
        """Park masked lanes at the exit block (idle until re-injected)."""
        return self.vm.park(state, mask)

    def inject(self, state: dict, mask, *args) -> dict:
        """Re-initialize masked lanes with fresh arguments.

        ``args`` follow the function's calling convention with full
        batched leading axes; only rows where ``mask`` is True are
        consumed.  In-flight (unmasked) lanes are untouched.
        """
        inputs, z = self._fn._bind(args)
        if z != self.batch_size:
            raise TypeError(
                f"stepper.inject: batch size {z} != {self.batch_size}"
            )
        return self.vm.inject(state, mask, self._ex._qualify(inputs))

    def depth_exceeded(self, state: dict) -> jax.Array:
        """``[batch]`` bool: lanes whose stacks overflowed ``max_depth``."""
        return self.vm.lane_depth_exceeded(state)

    def outputs(self, state: dict) -> Any:
        """The output pytree view of a snapshot (no overflow check).

        Rows of lanes that have halted are final; rows of in-flight lanes
        are whatever the program has written so far.  Always in the
        caller's original lane order (compaction is inverted here).
        """
        iface = self._fn._iface
        main = self._ex.main
        return jax.tree_util.tree_unflatten(
            iface.out_treedef,
            [
                # read_top is layout-transparent: an output packed into a
                # grouped array (pgo=) is sliced out of its slot here.
                self.vm.unpermute(
                    state, self.vm.read_top(state, ir.qualify(main, name))
                )
                for name in iface.out_leaves
            ],
        )

    def result(self, state: dict) -> Any:
        """Final outputs with the fault checks of a plain call.

        Under ``on_fault="raise"`` raises :class:`pc_vm.StackOverflow` if
        any lane's stacks exceeded ``max_depth``, or
        :class:`pc_vm.LaneFault` if an enabled detector (non-finite /
        watchdog) tripped — their results would be silently invalid.
        Under ``on_fault="quarantine"`` never raises: inspect
        ``fault_code(state)`` for the per-lane verdicts.
        """
        cfg = self.vm.config
        if cfg.on_fault == "raise":
            # Lane order matters: the exceptions name offending lanes.
            _raise_if_overflowed(
                jax.device_get(self.vm.lane_depth_exceeded(state)),
                self.batch_size, cfg.max_depth,
                self._ex.overflow_hint,
            )
            if cfg.detect_nonfinite or cfg.lane_step_budget is not None:
                _raise_if_faulted(
                    jax.device_get(self.vm.lane_fault(state)),
                    self.batch_size,
                )
        return self.outputs(state)


# --------------------------------------------------------------------------
# The autobatched callable
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class CacheInfo:
    hits: int
    misses: int
    entries: int
    lowerings: int
    traces: int


class AutobatchedFunction:
    """A batched callable over positional pytree arguments.

    Produced by :func:`autobatch`; do not construct directly.  Calling it
    flattens each positional argument against its declared
    ``Batched``/``Shared`` spec, broadcasts shared leaves across the batch,
    runs the backend, and unflattens the flat IR outputs into the declared
    result pytree.
    """

    def __init__(
        self,
        *,
        registry: ast_frontend.Namespace,
        main: str,
        program: Optional[ir.Program],
        iface_args: tuple[ir.ArgBinding, ...],
        arg_specs: dict[str, jax.ShapeDtypeStruct],
        out_treedef,
        out_leaves: tuple[str, ...],
        backend: str,
        batch_size: Optional[int],
        max_depth: Optional[int],
        max_steps: int,
        use_kernel: bool,
        collect_stats: bool,
        schedule: str,
        fuse: bool,
        mesh: Any = None,
        verify: bool = False,
        dce: bool = False,
        on_fault: str = "raise",
        detect_nonfinite: bool = False,
        lane_step_budget: Optional[int] = None,
        compact_every: Optional[int] = None,
        trace: Any = None,
        pgo: Any = None,
    ):
        if backend not in BACKENDS:
            raise ValueError(f"backend must be one of {BACKENDS}, got {backend!r}")
        if schedule not in pc_vm.SCHEDULES:
            raise ValueError(
                f"schedule must be one of {pc_vm.SCHEDULES}, got {schedule!r}"
            )
        if on_fault not in pc_vm.ON_FAULT:
            raise ValueError(
                f"on_fault must be one of {pc_vm.ON_FAULT}, got {on_fault!r}"
            )
        self.registry = registry
        self.main = main
        self.backend = backend
        self.batch_size = batch_size
        self.schedule = schedule
        self.fuse = fuse
        self.mesh = mesh
        self.verify = verify
        self.dce = dce
        self.on_fault = on_fault
        self.detect_nonfinite = detect_nonfinite
        self.lane_step_budget = lane_step_budget
        self.compact_every = compact_every
        self.trace = trace
        self.pgo = _as_profile(pgo)
        self.max_depth = max_depth  # None: use the static bound (pc)
        # Resolved lazily (resolving may initialize the jax backend, which
        # a decorator at module import time must not do).
        self._mesh_key_cache: Optional[tuple] = None
        self._program = program
        self._iface = ir.Interface(
            args=iface_args, out_treedef=out_treedef, out_leaves=out_leaves
        )
        self._arg_specs = arg_specs
        self._vm_opts = dict(
            max_steps=max_steps, use_kernel=use_kernel,
            collect_block_stats=collect_stats, schedule=schedule, mesh=mesh,
            on_fault=on_fault, detect_nonfinite=detect_nonfinite,
            lane_step_budget=lane_step_budget, compact_every=compact_every,
            trace=trace,
        )
        # Constructor kwargs, for with_options() cloning.  iface pieces
        # are stored unflattened so a clone rebuilds an identical wrapper.
        self._init_kwargs = dict(
            registry=registry, main=main, program=program,
            iface_args=iface_args, arg_specs=arg_specs,
            out_treedef=out_treedef, out_leaves=out_leaves,
            backend=backend, batch_size=batch_size, max_depth=max_depth,
            max_steps=max_steps, use_kernel=use_kernel,
            collect_stats=collect_stats, schedule=schedule, fuse=fuse,
            mesh=mesh, verify=verify, dce=dce, on_fault=on_fault,
            detect_nonfinite=detect_nonfinite,
            lane_step_budget=lane_step_budget, compact_every=compact_every,
            trace=trace, pgo=self.pgo,
        )
        # Caches + instrumentation.
        self._lowered: Optional[ir.LoweredProgram] = None
        self._depth_report: Optional[analysis.StackDepthReport] = None
        self._executors: dict[int, Any] = {}
        self._aval_cache: dict[tuple, Any] = {}
        self._hits = 0
        self._misses = 0
        self._lower_count = 0
        self._trace_count = 0
        self._last_executor = None
        # Pins: what this wrapper re-asserts into the namespace before its
        # (lazy) first trace, so it always traces *its own* definition even
        # if another registration shadowed the name afterwards.  The
        # decorator path pins (fn, param_specs, output_specs); the builder
        # paths pin the ir.Function objects they registered.
        self._pinned: Optional[tuple] = None
        self._pinned_funcs: dict[str, ir.Function] = {}
        self.__name__ = main

    # ------------------------------------------------------------------
    # Program / lowering / executor caches
    # ------------------------------------------------------------------

    @property
    def program(self) -> ir.Program:
        """The traced Fig-2 IR program (traced once, then cached)."""
        if self._program is None:
            # Re-assert pinned definitions: shadowing is last-wins for
            # *name lookups*, but a wrapper always runs what it wrapped.
            if self._pinned is not None:
                fn, param_specs, output_specs = self._pinned
                if self.registry._pyfns.get(self.main) is not fn:
                    self.registry.define(param_specs, output_specs)(fn)
            for fname, func in self._pinned_funcs.items():
                if self.registry._built.get(fname) is not func:
                    self.registry.add(func)
            self._program = self.registry.trace(self.main)
            self._trace_count += 1
        main_fn = self._program.functions[self._program.main]
        if main_fn.iface is not self._iface:
            # Record *this* wrapper's calling convention on the IR without
            # mutating a Function that other wrappers (or the caller's own
            # Program object) may share.
            self._program = ir.Program(
                functions={
                    **self._program.functions,
                    self._program.main: ir.dataclass_replace(
                        main_fn, iface=self._iface
                    ),
                },
                main=self._program.main,
            )
        return self._program

    @property
    def lowered(self) -> ir.LoweredProgram:
        """The merged stack-explicit program (pc backend; lowered once).

        When ``fuse=True`` (the default) the superblock fusion passes run
        as part of this single lowering, so all batch sizes share the
        fused program; ``dce=True`` appends the dead-code-elimination
        pass, and ``verify=True`` runs the lowered-IR verifier between
        every pass of the pipeline.  With ``pgo=`` set, the profile-guided
        passes (``passes.pgo_passes``: trace-driven superblock formation,
        hot-state layout packing, block reordering) run last — the profile
        must have been collected from *this* fuse/dce configuration, since
        its per-block counts are matched against the block graph here.
        """
        if self._lowered is None:
            low = lowering.lower(self.program, verify=self.verify)
            post: list = []
            if self.fuse:
                post.extend(passes.fusion_passes())
            if self.dce:
                post.append(passes.DeadCodeElimination())
            if self.pgo is not None:
                post.extend(passes.pgo_passes(self.pgo))
            if post:
                low = passes.PassPipeline(
                    post, verify=self.verify, debug=self.verify
                ).run(low)
            self._lowered = low
            self._lower_count += 1
        return self._lowered

    @property
    def depth_report(self) -> analysis.StackDepthReport:
        """Static worst-case stack usage of the lowered program (pc)."""
        if self._depth_report is None:
            self._depth_report = analysis.stack_depth_bound(self.lowered)
        return self._depth_report

    @property
    def resolved_max_depth(self) -> int:
        """The ``max_depth`` the VM actually runs with.

        An explicit ``max_depth=`` wins.  With ``max_depth=None``, the
        statically inferred bound (``depth_report.required_max_depth``)
        applies; a recursive program has no static bound and falls back
        to :data:`DEFAULT_MAX_DEPTH`.
        """
        if self.max_depth is not None:
            return self.max_depth
        rep = self.depth_report
        if rep.required_max_depth is None:
            return DEFAULT_MAX_DEPTH
        return rep.required_max_depth

    def _overflow_hint(self) -> str:
        """Actionable guidance for StackOverflow, from the static bound."""
        rep = self.depth_report
        if rep.recursive_cycle is not None:
            cyc = " -> ".join(rep.recursive_cycle + rep.recursive_cycle[:1])
            return (
                f"The program is recursive ({cyc}), so the required depth "
                "depends on the inputs; pass a larger max_depth= to "
                "autobatch()."
            )
        return (
            "The statically inferred bound for this program is "
            f"max_depth={rep.required_max_depth}; pass max_depth= at least "
            "that (or max_depth=None to use the bound) to autobatch()."
        )

    def diagnostics(self) -> passes.Diagnostics:
        """Verifier + static-analysis report over the lowered program.

        pc backend only (the other backends never lower).  See
        :func:`repro.core.passes.diagnose`; ``tools/irlint.py`` prints the
        same report from the command line.
        """
        if self.backend != "pc":
            raise ValueError("diagnostics() requires the 'pc' backend")
        return passes.diagnose(self.lowered)

    def _executor(self, z: int):
        ex = self._executors.get(z)
        if ex is not None:
            return ex
        if self.backend == "pc":
            ex = _PcExecutor(
                self.lowered, self.program.main,
                pc_vm.VMConfig(
                    batch_size=z, max_depth=self.resolved_max_depth,
                    **self._vm_opts,
                ),
                overflow_hint=self._overflow_hint(),
            )
        elif self.backend in ("local", "local_eager"):
            ex = _LocalExecutor(
                self.program, z, jit_blocks=(self.backend == "local")
            )
        else:
            ex = _ReferenceExecutor(self.program, z)
        self._executors[z] = ex
        return ex

    def with_options(self, **overrides: Any) -> "AutobatchedFunction":
        """A clone of this wrapper with some pc knobs changed.

        ``overrides`` take the :func:`autobatch` keyword names (e.g.
        ``trace=4096``, ``schedule="lookahead"``, ``collect_stats=False``).
        The clone shares the traced IR program — and, when ``fuse``/
        ``dce``/``verify`` are unchanged, the lowering — so turning a knob
        costs at most a recompile, never a retrace.  This is how tooling
        (``tools/vmtrace.py``) turns tracing on for an existing
        ``@autobatch`` function without editing its decoration.
        """
        unknown = set(overrides) - set(self._init_kwargs)
        if unknown:
            raise TypeError(
                f"with_options: unknown option(s) {sorted(unknown)}; "
                f"valid names: {sorted(self._init_kwargs)}"
            )
        kw = dict(self._init_kwargs)
        kw.update(overrides)
        clone = AutobatchedFunction(**kw)
        clone._pinned = self._pinned
        clone._pinned_funcs = dict(self._pinned_funcs)
        clone._program = self._program
        if (
            all(
                kw[k] == self._init_kwargs[k]
                for k in ("fuse", "dce", "verify")
            )
            and clone._pgo_digest() == self._pgo_digest()
        ):
            clone._lowered = self._lowered
            clone._depth_report = self._depth_report
        return clone

    def optimize(self, profile: Any) -> "AutobatchedFunction":
        """A clone re-lowered through the profile-guided pipeline.

        ``profile`` is a :class:`repro.obs.blockprof.BlockProfile` (or a
        path to one saved as JSON) collected from a traced run of *this*
        wrapper — typically ``BlockProfile.from_trace(fn.last_trace)``
        after a call with ``trace=`` on.  Equivalent to
        ``fn.with_options(pgo=profile)``: the clone shares the traced IR,
        re-lowers once through ``passes.pgo_passes`` and compiles its own
        executors (the profile digest is part of the cache key).
        """
        return self.with_options(pgo=profile)

    def cache_info(self) -> CacheInfo:
        """Executor/compile cache counters.

        ``hits``/``misses`` count calls against the ``(backend, batch_size,
        input avals)`` key; ``lowerings`` counts stack-explicit lowerings
        (at most 1 per function regardless of how many batch sizes were
        run); ``traces`` counts frontend traces.
        """
        return CacheInfo(
            hits=self._hits,
            misses=self._misses,
            entries=len(self._aval_cache),
            lowerings=self._lower_count,
            traces=self._trace_count,
        )

    # ------------------------------------------------------------------
    # Argument binding
    # ------------------------------------------------------------------

    def _bind(self, args: tuple) -> tuple[dict[str, jax.Array], int]:
        iface = self._iface
        if len(args) != len(iface.args):
            raise TypeError(
                f"{self.main}() takes {len(iface.args)} positional "
                f"argument(s), got {len(args)}"
            )
        flat: list[tuple[ir.ArgBinding, list]] = []
        for i, (binding, arg) in enumerate(zip(iface.args, args)):
            leaves, treedef = jax.tree_util.tree_flatten(arg)
            if treedef != binding.treedef:
                raise TypeError(
                    f"{self.main}() argument {i}: pytree structure "
                    f"{treedef} does not match declared {binding.treedef}"
                )
            flat.append((binding, leaves))
        # Infer the batch size from the first batched leaf.
        z = self.batch_size
        for binding, leaves in flat:
            if binding.shared:
                continue
            for name, leaf in zip(binding.params, leaves):
                spec = self._arg_specs[name]
                shape = jnp.shape(leaf)
                if len(shape) != len(spec.shape) + 1:
                    raise TypeError(
                        f"{self.main}() batched leaf {name!r}: expected a "
                        f"leading batch axis over {tuple(spec.shape)}, got "
                        f"shape {shape}"
                    )
                if z is None:
                    z = int(shape[0])
                elif shape[0] != z:
                    raise TypeError(
                        f"{self.main}() batched leaf {name!r}: batch axis "
                        f"{shape[0]} != {z}"
                    )
        if z is None:
            raise TypeError(
                f"{self.main}() has no Batched arguments; pass "
                "batch_size= to autobatch()"
            )
        inputs: dict[str, jax.Array] = {}
        for binding, leaves in flat:
            for name, leaf in zip(binding.params, leaves):
                spec = self._arg_specs[name]
                x = jnp.asarray(leaf, spec.dtype)
                if binding.shared:
                    if tuple(x.shape) != tuple(spec.shape):
                        raise TypeError(
                            f"{self.main}() shared leaf {name!r}: expected "
                            f"shape {tuple(spec.shape)}, got {tuple(x.shape)}"
                        )
                    x = jnp.broadcast_to(x, (z,) + tuple(spec.shape))
                elif tuple(x.shape) != (z,) + tuple(spec.shape):
                    raise TypeError(
                        f"{self.main}() batched leaf {name!r}: expected "
                        f"shape {(z,) + tuple(spec.shape)}, got "
                        f"{tuple(x.shape)}"
                    )
                inputs[name] = x
        return inputs, z

    def _trace_key(self) -> Optional[int]:
        """Hashable trace identity (the resolved ring capacity)."""
        if self.backend != "pc":
            return None
        from repro.obs.trace import resolve_capacity

        return resolve_capacity(self.trace)

    def _pgo_digest(self) -> Optional[str]:
        """Hashable identity of the guiding profile (None = no PGO)."""
        return None if self.pgo is None else self.pgo.digest()

    def _mesh_key(self) -> Optional[tuple]:
        """Hashable mesh identity (resolved once, at first call time).

        Only the pc backend shards; for the others mesh is ignored
        entirely (like schedule/fuse) and never resolved against the
        device set.
        """
        if self.backend != "pc":
            return None
        if self.mesh is not None and self._mesh_key_cache is None:
            self._mesh_key_cache = pc_vm.mesh_cache_key(self.mesh)
        return self._mesh_key_cache

    def _aval_key(self, inputs: dict[str, jax.Array], z: int) -> tuple:
        # Note: _bind forces every leaf to (z,)+spec.shape / spec.dtype, so
        # today these keys collapse to the batch size; they are kept in
        # full aval form so the cache contract survives future shape- or
        # dtype-polymorphic specs.  schedule/fuse/mesh and the fault knobs
        # are fixed per wrapper but belong to the key contract: two
        # wrappers over the same program with different knobs must never
        # share a compiled executor.
        return (
            self.backend,
            z,
            self.schedule,
            self.fuse,
            self.verify,
            self.dce,
            self.on_fault,
            self.detect_nonfinite,
            self.lane_step_budget,
            self.compact_every,
            self._trace_key(),
            self._mesh_key(),
            self._pgo_digest(),
            tuple(
                (k, tuple(jnp.shape(v)), str(jnp.asarray(v).dtype))
                for k, v in sorted(inputs.items())
            ),
        )

    # ------------------------------------------------------------------
    # Execution
    # ------------------------------------------------------------------

    def __call__(self, *args):
        inputs, z = self._bind(args)
        key = self._aval_key(inputs, z)
        ex = self._aval_cache.get(key)
        if ex is None:
            self._misses += 1
            ex = self._executor(z)
            self._aval_cache[key] = ex
        else:
            self._hits += 1
        self._last_executor = ex
        out = ex.run(inputs)
        return jax.tree_util.tree_unflatten(
            self._iface.out_treedef,
            [out[name] for name in self._iface.out_leaves],
        )

    def lower(self, *args) -> AotLowered:
        """AOT-lower the full batched computation for these avals (pc only)."""
        if self.backend != "pc":
            raise ValueError("AOT lowering requires the 'pc' backend")
        inputs, z = self._bind(args)
        return AotLowered(self._executor(z).lower(inputs))

    def stepper(self, *args) -> Stepper:
        """A :class:`Stepper` for segmented (resumable) execution (pc only).

        Cache-keyed like :meth:`lower`: the stepper shares the per-batch-
        size executor (and its compiled VM) with plain calls, so creating
        one after calling the function costs no extra trace/lower/compile.
        """
        if self.backend != "pc":
            raise ValueError("stepper requires the 'pc' backend")
        inputs, z = self._bind(args)
        return Stepper(self, inputs, z)

    # ------------------------------------------------------------------
    # Introspection
    # ------------------------------------------------------------------

    @property
    def last_result(self) -> Optional[pc_vm.VMResult]:
        """The :class:`pc_vm.VMResult` of the most recent pc-backend call."""
        return self._last_executor.last_result if self._last_executor else None

    @property
    def last_trace(self):
        """The :class:`repro.obs.trace.DispatchTrace` of the most recent
        pc-backend call, or ``None`` (no call yet, or ``trace=`` unset)."""
        res = self.last_result
        return res.trace if res is not None else None

    @property
    def scheduler_stats(self) -> Optional[pc_vm.SchedulerStats]:
        """Scheduling summary of the most recent pc-backend call: schedule
        name, fused-or-not, block count, VM steps, mean dispatch occupancy,
        and the fused-block provenance map.  ``None`` before any pc run."""
        res = self.last_result
        return res.sched if res is not None else None

    @property
    def tag_stats(self) -> dict[str, tuple[int, int]]:
        """tag -> (primitive executions, active member-executions).

        Unified across backends: counters cover the *most recent call only*
        on every backend; ``{}`` before any call has run (and always for
        the ``reference`` backend, which keeps no counters).
        """
        return self._last_executor.tag_stats if self._last_executor else {}

    @property
    def utilization(self) -> dict[str, float]:
        """Per-tag batch utilization of the last run (paper Fig. 6).

        ``utilization[tag] = active / (executions * batch_size)``.  Returns
        ``{}`` before any call has run on every backend; tags that executed
        with no active members report ``0.0``.
        """
        ex = self._last_executor
        if ex is None:
            return {}
        z = ex.batch_size
        return {
            tag: (act / (execs * z) if execs else 0.0)
            for tag, (execs, act) in ex.tag_stats.items()
        }


# --------------------------------------------------------------------------
# Interface construction
# --------------------------------------------------------------------------


# The decorator path's out_leaves must match the output names the AST
# transform generates — share the single definition.
_ret_names = ast_frontend._ret_names


def _bind_in_specs(
    name: str,
    params: tuple[str, ...],
    in_specs: Sequence,
    declared: Optional[dict[str, jax.ShapeDtypeStruct]] = None,
) -> tuple[tuple[ir.ArgBinding, ...], dict[str, jax.ShapeDtypeStruct]]:
    """Map ``in_specs`` entries onto IR parameters in flatten order."""
    bindings: list[ir.ArgBinding] = []
    arg_specs: dict[str, jax.ShapeDtypeStruct] = {}
    idx = 0
    for entry in in_specs:
        leaf_specs, treedef, shared = _flatten_spec(entry)
        names = params[idx : idx + len(leaf_specs)]
        if len(names) != len(leaf_specs):
            raise TypeError(
                f"{name}: in_specs bind {idx + len(leaf_specs)} leaves but "
                f"the function has only {len(params)} parameters"
            )
        for p, spec in zip(names, leaf_specs):
            if declared is not None and not _specs_eq(spec, declared[p]):
                raise TypeError(
                    f"{name}: in_specs leaf for parameter {p!r} is {spec} "
                    f"but the program declares {declared[p]}"
                )
            arg_specs[p] = spec
        bindings.append(ir.ArgBinding(tuple(names), treedef, shared))
        idx += len(leaf_specs)
    if idx != len(params):
        raise TypeError(
            f"{name}: in_specs cover {idx} of {len(params)} parameters "
            f"({params[idx:]} unbound)"
        )
    return tuple(bindings), arg_specs


def _contains_dict(tree: Any) -> bool:
    if isinstance(tree, dict):
        return True
    if isinstance(tree, (list, tuple)):
        return any(_contains_dict(x) for x in tree)
    return False


def _bind_out_spec(
    name: str,
    outputs: tuple[str, ...],
    out_spec: Any,
    declared: Optional[dict[str, jax.ShapeDtypeStruct]] = None,
):
    """Resolve the output pytree -> (treedef, IR output names per leaf)."""
    if out_spec is None:
        # Default: a dict keyed by the IR output names.
        tree = {o: o for o in outputs}
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        return treedef, tuple(leaves)
    leaves, treedef = jax.tree_util.tree_flatten(out_spec)
    if all(isinstance(l, str) for l in leaves):
        # Name-based restructuring: leaves name IR outputs.
        for l in leaves:
            if l not in outputs:
                raise TypeError(
                    f"{name}: out_spec names unknown output {l!r} "
                    f"(have {outputs})"
                )
        return treedef, tuple(leaves)
    # Spec leaves: positional against the declared outputs in flatten order.
    # Unordered containers would bind in sorted-key order, silently
    # permuting equal-spec outputs — require name-based string leaves there.
    if _contains_dict(out_spec):
        raise TypeError(
            f"{name}: out_spec dicts with spec leaves are ambiguous "
            "(dict flatten order is sorted-key, not declaration order); "
            "use output-name strings as leaves, e.g. "
            "out_spec={'mean': 'sum_theta'}"
        )
    if len(leaves) != len(outputs):
        raise TypeError(
            f"{name}: out_spec has {len(leaves)} leaves for "
            f"{len(outputs)} outputs"
        )
    if declared is not None:
        for o, l in zip(outputs, leaves):
            spec = _as_spec(l)
            if not _specs_eq(spec, declared[o]):
                raise TypeError(
                    f"{name}: out_spec leaf for output {o!r} is {spec} "
                    f"but the program declares {declared[o]}"
                )
    return treedef, tuple(outputs)


# --------------------------------------------------------------------------
# The decorator / entry point
# --------------------------------------------------------------------------


def autobatch(
    target: Any = None,
    *,
    in_specs: Optional[Sequence] = None,
    out_spec: Any = None,
    backend: str = "pc",
    batch_size: Optional[int] = None,
    max_depth: Optional[int] = None,
    max_steps: int = 1_000_000,
    use_kernel: bool = False,
    collect_stats: bool = True,
    schedule: str = "earliest",
    fuse: bool = True,
    mesh: Any = None,
    verify: bool = False,
    dce: bool = True,
    on_fault: str = "raise",
    detect_nonfinite: bool = False,
    lane_step_budget: Optional[int] = None,
    compact_every: Optional[int] = None,
    trace: Any = None,
    pgo: Any = None,
    registry: Optional[ast_frontend.Namespace] = None,
):
    """Autobatch a restricted-Python function or an IR program.

    Usable three ways:

    1. As a decorator over restricted Python (``in_specs``/``out_spec``
       required; each parameter must be a single-leaf spec)::

           @autobatch(in_specs=(Batched(I32),), out_spec=I32)
           def fib(n): ...

    2. Over a :class:`frontend.ProgramBuilder`, a single
       :class:`frontend.FunctionBuilder` / :class:`ir.Function`, or a
       pre-built :class:`ir.Program`.  ``in_specs`` defaults to
       ``Batched(<declared spec>)`` per parameter; ``out_spec`` defaults to
       a dict keyed by the IR output names (pass a pytree of output-name
       strings to restructure, or of specs bound positionally).

    3. Partially applied (``autobatch(backend=..., ...)``) to get a
       decorator with fixed options.

    ``batch_size=None`` (the default) infers the batch size from the leading
    axis of the first ``Batched`` leaf on every call; executors are cached
    per batch size, and the pc backend's lowering is shared across all of
    them.  All functions registered in the same ``registry`` may call each
    other, whichever frontend defined them.  Decorated Python functions
    default to a process-wide namespace; builder programs default to a
    private one (pass ``registry=`` to share deliberately).

    pc-backend performance knobs (ignored by the other backends; all are
    part of the executor cache key, and all are bit-exact):

    * ``fuse=True`` runs the superblock fusion pass (fusion.py) over the
      stack-explicit lowering, collapsing straight-line jump chains into
      single VM dispatch steps;
    * ``schedule`` picks the VM's next-block policy: ``"earliest"`` (paper
      Algorithm 2), ``"popular"`` (occupancy argmax), ``"sweep"`` (every
      resident block once per loop iteration, no ``lax.switch``) or
      ``"lookahead"`` (occupancy argmax over each block plus its CFG
      successors — re-converges divergent lanes faster than plain
      ``"popular"``);
    * ``compact_every=k`` permutes the lane axis every ``k`` VM dispatches
      so lanes at the same program point occupy contiguous SIMD tiles
      (occupancy-aware lane compaction).  Lane identity is tracked and
      inverted on every output/Stepper/fault surface, so results are
      bit-exact with ``compact_every=None`` (the default: no compaction);
    * ``use_kernel=True`` routes stack pushes/peeks through the Pallas
      ``stack_ops`` kernels (interpret mode off-TPU).  Composes with
      ``mesh``: each device runs the kernel over its own lane slice;
    * ``mesh`` shards the batch-lane axis of every VM state array across
      devices (``None`` = single device, an int device count, or a 1-D
      ``jax.sharding.Mesh``), compiling the whole program as one SPMD
      ``lax.while_loop``; the batch size must divide across the mesh;
    * ``dce=True`` runs the dead-code-elimination pass over the lowered
      program, dropping primitives whose outputs are never observed and
      shrinking the VM state the masked updates touch every dispatch;
    * ``verify=True`` runs the lowered-IR verifier (verifier.py) between
      every pass of the lowering/fusion pipeline;
    * ``max_depth=None`` (the default) sizes the pc/variable stacks from
      the static interprocedural bound (``fn.depth_report``); recursive
      programs have no static bound and fall back to
      ``DEFAULT_MAX_DEPTH=32`` — pass an explicit ``max_depth=`` there
      (a stack overflow names the recursive cycle);
    * ``trace=`` records a per-dispatch trace into a fixed-capacity
      on-device ring buffer (``True`` = the default capacity, an int =
      that many events).  Purely observational — outputs, step counts
      and the dispatch sequence are bit-exact with ``trace=None``.  Read
      it via ``fn.last_trace`` / ``Stepper.trace(state)`` as a
      :class:`repro.obs.trace.DispatchTrace`; render timelines with
      ``repro.obs.timeline`` (see ``docs/observability.md``);
    * ``pgo=`` re-lowers through the profile-guided pipeline
      (``passes.pgo_passes``): a :class:`repro.obs.blockprof.BlockProfile`
      (or a path to one saved as JSON) drives trace-driven superblock
      formation (hot call frames merged or tail-duplicated inline),
      hot-state layout packing (same-dtype state variables grouped into
      one packed array, cutting masked updates per dispatch) and block
      reordering by dispatch frequency.  Outputs stay bit-exact; the
      profile digest is part of the executor cache key.  Collect a
      profile from a traced run and apply it with ``fn.optimize(prof)``
      (``== fn.with_options(pgo=prof)``), or use ``tools/pgo.py``.

    Fault containment knobs (pc backend; also part of the cache key):

    * ``on_fault="raise"`` (the default) keeps faults batch-fatal: the
      executor raises :class:`pc_vm.StackOverflow` (with the per-lane mask
      and lane indices as attributes) or :class:`pc_vm.LaneFault` after
      the run.  ``on_fault="quarantine"`` contains faults per lane: a
      faulted lane is parked out of the liveness mask, the batch never
      aborts, healthy lanes stay bit-exact with a fault-free run, and the
      per-lane verdicts are exposed via ``fn.last_result.fault_code`` /
      ``Stepper.fault_code`` (codes index ``pc_vm.FAULT_NAMES``);
    * ``detect_nonfinite=True`` checks every masked state write of inexact
      dtype for NaN/Inf and faults the writing lane (``NONFINITE``);
    * ``lane_step_budget=N`` arms a per-lane watchdog: a lane active for
      more than ``N`` block dispatches without halting faults
      (``WATCHDOG``) — the guard against data-dependent livelock.
    """
    if target is None:
        return functools.partial(
            autobatch,
            in_specs=in_specs,
            out_spec=out_spec,
            backend=backend,
            batch_size=batch_size,
            max_depth=max_depth,
            max_steps=max_steps,
            use_kernel=use_kernel,
            collect_stats=collect_stats,
            schedule=schedule,
            fuse=fuse,
            mesh=mesh,
            verify=verify,
            dce=dce,
            on_fault=on_fault,
            detect_nonfinite=detect_nonfinite,
            lane_step_budget=lane_step_budget,
            compact_every=compact_every,
            trace=trace,
            pgo=pgo,
            registry=registry,
        )
    if registry is not None:
        ns = registry
    elif isinstance(
        target, (frontend.ProgramBuilder, frontend.FunctionBuilder,
                 ir.Function)
    ):
        # Builder programs default to a private namespace: registering
        # their function names into the process-wide one could silently
        # shadow the callees of not-yet-traced decorated functions.  Pass
        # registry= to share a namespace deliberately (e.g. for AST <->
        # builder cross-calls).
        ns = ast_frontend.Namespace()
    else:
        ns = DEFAULT_NAMESPACE
    opts = dict(
        backend=backend, batch_size=batch_size, max_depth=max_depth,
        max_steps=max_steps, use_kernel=use_kernel, collect_stats=collect_stats,
        schedule=schedule, fuse=fuse, mesh=mesh, verify=verify, dce=dce,
        on_fault=on_fault, detect_nonfinite=detect_nonfinite,
        lane_step_budget=lane_step_budget, compact_every=compact_every,
        trace=trace, pgo=pgo,
    )

    program: Optional[ir.Program] = None
    pinned_funcs: dict[str, ir.Function] = {}
    if isinstance(target, frontend.ProgramBuilder):
        # Feed the builder's functions through the unified namespace so they
        # can call (and be called by) AST-defined functions.
        for func in target.functions.values():
            pinned_funcs[func.name] = ns.add(func)
        main_fn = ns._built[target.main]
        main = target.main
    elif isinstance(target, (frontend.FunctionBuilder, ir.Function)):
        main_fn = ns.add(target)
        main = main_fn.name
        pinned_funcs[main] = main_fn
    elif isinstance(target, ir.Program):
        program = target
        main = target.main
        main_fn = target.functions[main]
    elif callable(target):
        return _autobatch_python(target, ns, in_specs, out_spec, opts)
    else:
        raise TypeError(f"cannot autobatch {target!r}")

    params, outputs = main_fn.params, main_fn.outputs
    if in_specs is None:
        in_specs = tuple(Batched(main_fn.param_specs[p]) for p in params)
    iface_args, arg_specs = _bind_in_specs(
        main, params, in_specs, declared=main_fn.param_specs
    )
    out_treedef, out_leaves = _bind_out_spec(
        main, outputs, out_spec, declared=main_fn.output_specs
    )
    wrapped = AutobatchedFunction(
        registry=ns, main=main, program=program,
        iface_args=iface_args, arg_specs=arg_specs,
        out_treedef=out_treedef, out_leaves=out_leaves, **opts,
    )
    wrapped._pinned_funcs = pinned_funcs
    return wrapped


def _autobatch_python(fn, ns, in_specs, out_spec, opts) -> AutobatchedFunction:
    name = fn.__name__
    params = tuple(inspect.signature(fn).parameters)
    if in_specs is None or out_spec is None:
        raise TypeError(
            f"@autobatch over Python function {name!r} requires in_specs= "
            "and out_spec= (output types of recursive functions cannot be "
            "inferred)"
        )
    iface_args, arg_specs = _bind_in_specs(name, params, in_specs)
    for binding in iface_args:
        if len(binding.params) != 1:
            raise TypeError(
                f"{name}: restricted-Python parameters must be single-leaf "
                f"specs (argument binding {binding.params} has "
                f"{len(binding.params)} leaves); use a FunctionBuilder "
                "program for multi-leaf pytree arguments"
            )
    if _contains_dict(out_spec):
        raise TypeError(
            f"{name}: out_spec dicts with spec leaves are ambiguous "
            "(dict flatten order is sorted-key, not declaration order, so "
            "returned values would bind to sorted keys); use a tuple "
            "out_spec and restructure at the call site"
        )
    out_leaf_specs = [
        _as_spec(l) for l in jax.tree_util.tree_flatten(out_spec)[0]
    ]
    outputs = _ret_names(len(out_leaf_specs))
    out_treedef = jax.tree_util.tree_flatten(out_spec)[1]
    param_specs = {p: arg_specs[p] for p in params}
    ns.define(param_specs=param_specs, output_specs=out_leaf_specs)(fn)
    wrapped = AutobatchedFunction(
        registry=ns, main=name, program=None,
        iface_args=iface_args, arg_specs=arg_specs,
        out_treedef=out_treedef, out_leaves=outputs, **opts,
    )
    wrapped._pinned = (fn, param_specs, out_leaf_specs)
    functools.update_wrapper(wrapped, fn, updated=())
    return wrapped
