"""Program-counter autobatching VM (paper Algorithm 2), TPU-native.

The whole batched program executes as ONE ``jax.lax.while_loop`` whose body

  1. picks the next block index via a pluggable *schedule* (see below),
  2. dispatches to that block's fused body via ``jax.lax.switch``,
  3. masks all state updates to the locally-active members.

Schedules (``VMConfig.schedule``):

* ``"earliest"`` — the paper's Algorithm 1/2 heuristic: the smallest block
  index any live member's pc-top points at.  Deterministic sweep order;
  members parked at later blocks wait.
* ``"popular"``  — the occupancy heuristic of Lao et al. (2020): the block
  where the most live members currently reside, maximizing SIMD occupancy
  per dispatch.  Ties break toward the lowest index.
* ``"sweep"``    — run *every* block once per loop iteration under its own
  mask, with no ``lax.switch`` at all.  Amortizes dispatch overhead for
  small (post-fusion) programs when members are spread across many blocks;
  one loop iteration can advance a member through several blocks.
* ``"lookahead"`` — occupancy over the block's CFG successors: score each
  resident block by ``2*count[b] + sum(count[s] for s in successors(b))``
  and dispatch the argmax.  Prefers blocks whose completion *feeds* other
  populated blocks, so divergent members re-converge sooner than under
  plain ``"popular"``.  Ties break toward the lowest index.

All schedules are bit-exact with each other and with the reference
interpreter: every block body masks its updates to the members whose pc-top
selects it, so per-member semantics are schedule-independent.

Because recursion is materialized into fixed-shape ``[depth, batch, ...]``
stack arrays, the VM contains no host control flow at all: it jits, lowers
and compiles like any other XLA program, and members at *different stack
depths* batch together whenever their pc-tops coincide (the paper's central
contribution).

Primitive-execution strategy is *masking* (`jnp.where` selects), which is
the TPU-friendly choice (see DESIGN.md §2).  Stack traffic — the only
gathers/scatters — is confined to pushes and pops thanks to the top-of-stack
cache (paper opt. iv), and can be routed through the Pallas ``stack_ops``
kernel on TPU (``use_kernel=True``).

Multi-device lane sharding (``VMConfig.mesh``):

Every piece of VM state is *lane-major* — ``[batch, ...]`` tops/pointers/
masks and ``[depth, batch, ...]`` stacks — and every block body is
elementwise per lane, so the whole step is embarrassingly data-parallel.
With ``mesh=N`` (or an explicit 1-D ``jax.sharding.Mesh``) the VM lays out
each state array with a ``NamedSharding`` that splits the lane axis across
the mesh, and the single ``lax.while_loop`` compiles as one SPMD program.
The only cross-device traffic per iteration is scalar all-reduces:

* the liveness check in ``cond`` (``any(pc_top < exit)`` — one bool),
* the schedule's block choice in ``_pick_block`` (``min``/``argmax`` over
  per-lane pc values — one i32),
* with ``collect_block_stats=True``, the per-dispatch occupancy count
  (one i32; disable stats to drop it).

All schedules stay bit-exact under sharding: block bodies are per-lane, and
the reductions above are integer min/sum/argmax, which are associative and
placement-independent.  (The lanes' own float arithmetic is exact too when
XLA compiles it the same at the per-device batch size; on TPU a contraction
it tiles by batch size is not, see ``docs/architecture.md``.)  The
loop-carried state is donated, so steady-state memory is flat at one copy
of the VM state.

Segmented (resumable) execution:

``run()`` executes to completion, but the VM can also run in *segments*:
``start()`` builds the initial state snapshot, ``run_segment(state, n)``
advances it by at most ``n`` loop iterations and returns the updated
snapshot, and ``result(state)`` materializes a :class:`VMResult` from any
snapshot.  The segment loop reuses the exact same body function as the
single-shot loop and the snapshot carries *all* execution state (pc
stack/top, variable tops/stacks/pointers, overflow flags, step and
occupancy counters), so chaining segments of any sizes is bit-exact with
a single ``run()`` — the loop merely observes an extra iteration bound in
its ``cond``.  Between segments the host may retire finished lanes
(``lane_done``), park idle ones (``park``), and re-initialize a masked
subset with fresh inputs (``inject``) — the primitive underneath
retire-and-refill continuous batching (see ``repro/serve/engine.py``).
Snapshots are donatable pytrees: every state-in/state-out entry point
donates its input snapshot, so steady-state memory stays flat at one copy
of the VM state.

Fault containment (``VMConfig.on_fault``):

Batch members run independently, so one misbehaving lane should not be
batch-fatal.  Every lane carries a fault code (``FAULT_OK`` /
``FAULT_STACK_OVERFLOW`` / ``FAULT_NONFINITE`` / ``FAULT_WATCHDOG``;
first fault wins) set when a push overflows ``max_depth``, when a masked
state write produces NaN/Inf (opt-in via ``detect_nonfinite``), or when a
lane stays active past ``lane_step_budget`` dispatches without halting
(opt-in watchdog against data-dependent livelock).  Under
``on_fault="quarantine"`` a faulted lane is excluded from every dispatch
mask and from the liveness reduction the iteration after it faults — its
state freezes, the batch keeps running, and healthy lanes stay bit-exact
with a fault-free run (masking already guarantees per-lane independence).
Under ``on_fault="raise"`` (the default) behavior is the historical
batch-fatal one: the executor raises :class:`StackOverflow` /
:class:`LaneFault` after the run, and an enabled detector halts the loop
early instead of spinning to ``max_steps``.  ``inject`` clears the fault
code and watchdog clock of refilled lanes.

Occupancy-aware lane compaction (``VMConfig.compact_every``):

Divergence scatters the members resident at a block across the lane axis,
so a dispatch touches many SIMD tiles that are mostly masked out.  With
``compact_every=k`` the loop body, every ``k`` dispatches, *permutes* the
whole lane-major state with a stable sort on (liveness, pc-top) — lanes at
the same program point become contiguous, and dead/quarantined lanes sink
to the high end.  A ``lane_ids`` state vector records which original lane
each row holds; every identity-bearing surface (``VMResult`` outputs and
per-lane flags, ``lane_done``/``lane_fault``, ``Stepper`` views) applies
the inverse permutation, and ``inject``/``park`` translate their
original-order masks and inputs into row order — so compaction is
invisible everywhere except throughput.  Because every schedule picks
blocks from a lane-permutation-invariant histogram/min, the dispatch
sequence, step counts and all outputs are bit-exact with the uncompacted
run (property-tested).  ``mean_occupancy`` is measured per SIMD tile of
:data:`OCCUPANCY_TILE` lanes: active lanes divided by the capacity of the
tiles that held at least one active lane — the quantity compaction
actually improves, and one that never charges fully-idle (parked,
quarantined, retired) tiles.

Dispatch tracing (``VMConfig.trace``):

With ``trace=`` set (``True`` or an int event capacity) the loop carry
gains a fixed-capacity on-device ring buffer that records, per dispatch:
the chosen block id, the per-block live-resident histogram, active /
live / quarantined lane counts, the occupied-tile capacity, whether
compaction ran, and the post-dispatch faulted-lane total.  Recording is
strictly *write-only* with respect to execution — no traced value feeds
back into ``cond``, ``_pick_block`` or any block body — so a traced run
is bit-exact with an untraced one (outputs, step counts, and the dispatch
sequence itself; property-tested across the schedule x fuse x mesh x
compact_every x use_kernel matrix).  Drain the buffer host-side with
:meth:`ProgramCounterVM.get_trace` (or ``VMResult.trace`` after
``run()``) into a typed :class:`repro.obs.trace.DispatchTrace`; the ring
index is ``steps % capacity``, so when a run outlives the capacity the
newest events win and the drain reports how many oldest were dropped.
Under a mesh the buffers are replicated; the per-event counts are the
same integer all-reduces the stats path uses, so tracing composes with
sharding, segments, compaction and quarantine.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh

from . import ir

Array = jax.Array
_I32 = jnp.int32


def _bcast(mask: Array, val: Array) -> Array:
    """Broadcast a [Z] bool mask against a [Z, ...] value."""
    return mask.reshape(mask.shape + (1,) * (val.ndim - 1))


def _masked(mask: Array, new: Array, old: Array) -> Array:
    return jnp.where(_bcast(mask, new), new, old)


def _scatter_push(stack: Array, ptr: Array, val: Array, mask: Array) -> Array:
    """Bury ``val`` at depth ``ptr`` for active rows. stack: [D, Z, ...]."""
    z = stack.shape[1]
    rows = jnp.where(mask, ptr, stack.shape[0])  # OOB rows dropped
    return stack.at[rows, jnp.arange(z)].set(val, mode="drop")


def _gather_top(stack: Array, ptr: Array) -> Array:
    z = stack.shape[1]
    return stack[jnp.clip(ptr, 0, stack.shape[0] - 1), jnp.arange(z)]


def _tile_capacity(mask: Array) -> Array:
    """Lane capacity of the OCCUPANCY_TILE-wide tiles holding >=1 set lane.

    ``mask``: [Z] bool -> i32 scalar.  Tiles are fixed windows over the
    global lane index, so the value is device-placement-independent.  A
    trailing partial tile contributes only its real width.
    """
    z = mask.shape[0]
    t = OCCUPANCY_TILE
    g = -(-z // t)  # ceil(z / t) tiles
    pad = g * t - z
    mp = jnp.pad(mask, (0, pad)) if pad else mask
    occupied = jnp.any(mp.reshape(g, t), axis=1)
    caps = jnp.full((g,), t, _I32)
    if pad:
        caps = caps.at[g - 1].set(t - pad)
    return jnp.sum(jnp.where(occupied, caps, 0)).astype(_I32)


SCHEDULES = ("earliest", "popular", "sweep", "lookahead")

#: SIMD tile width (lanes) used by the occupancy metric: a dispatch's
#: occupancy is active lanes / capacity of the tiles holding at least one
#: active lane.  8 models vector-register granularity; the exact width only
#: scales the metric, it does not change which schedule/compaction wins.
OCCUPANCY_TILE = 8

#: Fault policies (``VMConfig.on_fault``): ``"raise"`` keeps the historical
#: batch-fatal behavior (the executor raises after the run); ``"quarantine"``
#: parks faulted lanes out of the liveness mask so the batch never aborts.
ON_FAULT = ("raise", "quarantine")

# Per-lane fault codes (i32, first fault wins; 0 = healthy).
FAULT_OK = 0
FAULT_STACK_OVERFLOW = 1  # a push landed at or beyond max_depth
FAULT_NONFINITE = 2  # a masked state write produced NaN/Inf (opt-in)
FAULT_WATCHDOG = 3  # lane exceeded its per-lane step budget (opt-in)

#: Human-readable names, indexed by fault code.
FAULT_NAMES = ("ok", "stack_overflow", "nonfinite", "watchdog")

#: Mesh axis name the lane (batch) dimension shards over.
LANE_AXIS = "lanes"


def resolve_mesh(mesh: Any) -> Optional[Mesh]:
    """Normalize a ``VMConfig.mesh`` value to a 1-D ``jax.sharding.Mesh``.

    Accepts ``None`` (no sharding), an integer device count (the first
    ``mesh`` entries of ``jax.devices()`` under the :data:`LANE_AXIS` axis),
    or an explicit 1-D ``Mesh`` whose single axis is the lane axis.
    """
    if mesh is None:
        return None
    if isinstance(mesh, Mesh):
        if len(mesh.axis_names) != 1:
            raise ValueError(
                "pc VM lane sharding needs a 1-D mesh (one axis over the "
                f"batch lanes); got axes {mesh.axis_names}"
            )
        return mesh
    n = int(mesh)
    if n < 1:
        raise ValueError(f"mesh device count must be >= 1, got {n}")
    devices = jax.devices()
    if n > len(devices):
        raise ValueError(
            f"mesh={n} needs {n} devices but only {len(devices)} are "
            "visible (on CPU, set XLA_FLAGS="
            "--xla_force_host_platform_device_count=N to fake a mesh)"
        )
    return Mesh(np.asarray(devices[:n]), (LANE_AXIS,))


def mesh_cache_key(mesh: Any) -> Optional[tuple]:
    """A hashable identity for a mesh spec, for compilation-cache keys.

    ``None`` stays ``None`` without touching the jax backend; everything
    else resolves to ``(axis_name, device ids)`` so that an int spec and
    the equivalent explicit ``Mesh`` share compiled executors.
    """
    m = resolve_mesh(mesh)
    if m is None:
        return None
    return (m.axis_names, tuple(d.id for d in m.devices.flat))


class StackOverflow(RuntimeError):
    """A member's pc or variable stack exceeded ``max_depth``.

    Out-of-range pushes are dropped (``mode="drop"``), so overflowing
    members produce invalid results while other members stay exact; the
    per-member ``VMResult.depth_exceeded`` flag records who overflowed.

    When raised by the batching executors, the exception carries the
    per-lane evidence as attributes: ``depth_exceeded`` is the ``[batch]``
    bool overflow mask (host ``numpy``), and ``lanes`` is the sorted array
    of offending lane indices — so callers can report *which* requests
    died instead of just that something did.
    """

    def __init__(
        self,
        message: str,
        *,
        depth_exceeded: Optional[np.ndarray] = None,
        lanes: Optional[np.ndarray] = None,
    ):
        super().__init__(message)
        self.depth_exceeded = depth_exceeded
        if lanes is None and depth_exceeded is not None:
            lanes = np.flatnonzero(np.asarray(depth_exceeded))
        self.lanes = lanes


class LaneFault(RuntimeError):
    """One or more lanes faulted (non-finite write or watchdog) under
    ``on_fault="raise"``.

    Attributes: ``fault_codes`` — the ``[batch]`` i32 code array (host
    ``numpy``, see :data:`FAULT_NAMES`); ``lanes`` — indices of the faulted
    lanes; ``faults`` — ``{lane: name}`` for the same lanes.
    """

    def __init__(self, message: str, *, fault_codes: np.ndarray):
        super().__init__(message)
        codes = np.asarray(fault_codes)
        self.fault_codes = codes
        self.lanes = np.flatnonzero(codes != FAULT_OK)
        self.faults = {
            int(i): FAULT_NAMES[int(codes[i])] for i in self.lanes
        }


@dataclass(frozen=True)
class VMConfig:
    batch_size: int
    max_depth: int = 32  # stack slots (usable call depth = max_depth - 1)
    max_steps: int = 1_000_000
    use_kernel: bool = False  # route stack traffic through Pallas stack_ops
    collect_block_stats: bool = True
    schedule: str = "earliest"  # one of SCHEDULES
    # Lane sharding: None (single device), an int device count, or a 1-D
    # jax.sharding.Mesh.  batch_size must divide evenly across the mesh.
    mesh: Any = None
    # Run the lowered-IR verifier (verifier.py) on the program before
    # compiling it — catches a broken transform before it becomes a wrong
    # batched answer.
    verify: bool = False
    # Fault containment.  "raise": faults are batch-fatal — the executor
    # raises StackOverflow/LaneFault after the run (historical behavior).
    # "quarantine": faulted lanes are excluded from the liveness mask and
    # from every block's dispatch mask the iteration after they fault, so
    # the batch keeps running and healthy lanes stay bit-exact with a
    # fault-free run.
    on_fault: str = "raise"
    # Opt-in finiteness check on masked state writes (inexact dtypes only):
    # a lane that writes NaN/Inf into VM state gets FAULT_NONFINITE.
    detect_nonfinite: bool = False
    # Opt-in watchdog against data-dependent livelock: a lane that stays
    # active for more than this many block dispatches without halting gets
    # FAULT_WATCHDOG.  None disables the check.
    lane_step_budget: Optional[int] = None
    # Occupancy-aware lane compaction: every `compact_every` dispatches the
    # loop body stably sorts the lane axis by (liveness, pc-top) so members
    # at the same program point occupy contiguous SIMD tiles.  None (the
    # default) disables compaction and skips all permutation bookkeeping.
    # Bit-exact with the uncompacted run (outputs, steps, fault codes,
    # per-lane ordering) for every schedule.
    compact_every: Optional[int] = None
    # Dispatch tracing: None/False disables, True uses the default ring
    # capacity (repro.obs.trace.DEFAULT_TRACE_CAPACITY events), an int is
    # an explicit capacity.  Purely observational — never changes outputs,
    # steps, or dispatch choices.  Drain with get_trace()/VMResult.trace.
    trace: Any = None

    def __post_init__(self):
        if self.on_fault not in ON_FAULT:
            raise ValueError(
                f"on_fault must be one of {ON_FAULT}, got {self.on_fault!r}"
            )
        if self.lane_step_budget is not None and self.lane_step_budget < 1:
            raise ValueError(
                "lane_step_budget must be >= 1 (or None to disable), got "
                f"{self.lane_step_budget}"
            )
        if self.compact_every is not None and self.compact_every < 1:
            raise ValueError(
                "compact_every must be >= 1 (or None to disable), got "
                f"{self.compact_every}"
            )
        # Normalizes True/int and raises on nonsense (capacity < 1).
        from repro.obs.trace import resolve_capacity

        resolve_capacity(self.trace)


@dataclass(frozen=True)
class SchedulerStats:
    """Per-run scheduling summary (host-side ints/floats, post-run).

    ``steps``/``mean_occupancy`` require a device sync and are therefore
    only materialized when ``collect_block_stats=True``; with stats off
    they are ``None``/``nan`` and the run's result stays async.
    """

    schedule: str
    fused: bool  # whether the program went through superblock fusion
    num_blocks: int
    steps: Optional[int]  # loop iterations (one sweep each for "sweep")
    # Tile-based SIMD occupancy: active lanes per dispatch / capacity of
    # the OCCUPANCY_TILE-lane tiles that held >= 1 active lane.  Excludes
    # fully-idle tiles, so parked/quarantined/retired lanes never dilute
    # it — and lane compaction (compact_every) genuinely raises it.
    mean_occupancy: float
    # Superblock provenance: fused block index -> original block indices
    # (None when the program was never fused).
    fused_from: Optional[dict[int, tuple[int, ...]]]
    # Devices the lane axis was sharded over (1 = unsharded).
    num_devices: int = 1
    # Legacy whole-batch metric: active members per dispatch / batch_size
    # (counts every lane in the denominator, live or not).  Kept for
    # trajectory comparisons with pre-compaction records.
    mean_lane_occupancy: float = float("nan")
    # The compaction cadence this run used (None = no compaction).
    compact_every: Optional[int] = None
    # Total masked whole-state top updates the run performed:
    # sum over blocks of block_exec[b] * (static masked-write count of
    # block b).  Requires collect_block_stats; None otherwise.  This is
    # the quantity StateLayoutPacking shrinks — packed members write one
    # grouped array instead of one `_masked` update per member.
    masked_updates: Optional[int] = None


@dataclass
class VMResult:
    outputs: dict[str, Array]
    steps: Array
    converged: Array  # bool: all members halted within max_steps
    block_exec: Optional[Array]  # [num_blocks] times each block ran
    block_active: Optional[Array]  # [num_blocks] total active members
    tag_stats: dict[str, tuple[int, int]]  # tag -> (execs, active) post-run
    depth_exceeded: Optional[Array] = None  # [batch] bool: stack overflowed
    sched: Optional[SchedulerStats] = None
    fault_code: Optional[Array] = None  # [batch] i32, see FAULT_NAMES
    lane_steps: Optional[Array] = None  # [batch] i32 active-dispatch counts
    # The drained dispatch trace (repro.obs.trace.DispatchTrace) when the
    # run had VMConfig.trace set; None otherwise.
    trace: Optional[Any] = None

    @property
    def fault_mask(self) -> Optional[Array]:
        """[batch] bool: lanes that faulted (None on legacy snapshots)."""
        if self.fault_code is None:
            return None
        return self.fault_code != FAULT_OK


class ProgramCounterVM:
    """Compiled batched executor for a :class:`ir.LoweredProgram`."""

    def __init__(self, lowered: ir.LoweredProgram, config: VMConfig):
        if config.schedule not in SCHEDULES:
            raise ValueError(
                f"schedule must be one of {SCHEDULES}, "
                f"got {config.schedule!r}"
            )
        # on_fault / lane_step_budget are validated by VMConfig itself.
        if config.verify:
            from . import verifier

            verifier.verify(lowered)
        self.lowered = lowered
        self.config = config
        self.num_blocks = len(lowered.blocks)
        # Dispatch-trace ring capacity (None = tracing off).  Resolved
        # once; the buffers live in the loop carry (see init_state).
        from repro.obs.trace import resolve_capacity

        self.trace_capacity = resolve_capacity(config.trace)
        self.mesh = resolve_mesh(config.mesh)
        self._lane_sharding = None
        self._stack_sharding = None
        self._replicated = None
        if self.mesh is not None:
            n = self.mesh.size
            if config.batch_size % n:
                raise ValueError(
                    f"batch_size={config.batch_size} does not divide across "
                    f"the {n}-device mesh; pick a batch that is a multiple "
                    f"of {n}"
                )
            # Lane-major layout rules live with the other sharding rules in
            # launch/sharding.py (one source of truth with the tests).
            from repro.launch.sharding import lane_shardings

            (
                self._lane_sharding,
                self._stack_sharding,
                self._replicated,
            ) = lane_shardings(self.mesh)
        # Pallas stack_ops binding.  Stack traffic is strictly per-lane, so
        # under a mesh the kernel runs shard-locally (one pallas_call per
        # device over its lane slice, via shard_map) — no cross-device
        # traffic, and bit-exact with the XLA scatter/gather path.
        self._kernel_push = self._kernel_peek = None
        if config.use_kernel:
            from repro.kernels.stack_ops import ops as _sk

            if self.mesh is None:
                self._kernel_push = _sk.masked_push
                self._kernel_peek = _sk.masked_peek
            else:
                self._kernel_push, self._kernel_peek = _sk.shard_local(
                    self.mesh
                )
        # "lookahead" scores blocks by occupancy over CFG successors; the
        # [B, B] 0/1 successor matrix is a trace-time constant.
        self._succ_matrix = None
        if config.schedule == "lookahead":
            succ = np.zeros((self.num_blocks, self.num_blocks), np.int32)
            for i, blk in enumerate(lowered.blocks):
                t = blk.term
                if isinstance(t, ir.LJump):
                    targets: tuple[int, ...] = (t.target,)
                elif isinstance(t, ir.LBranch):
                    targets = (t.true, t.false)
                elif isinstance(t, ir.LPushJump):
                    # One-step successor is the callee entry; the return
                    # site is reached only after the callee finishes.
                    targets = (t.target,)
                else:  # LReturn: dynamic target (the buried return pc).
                    targets = ()
                for s in targets:
                    if 0 <= s < self.num_blocks:
                        succ[i, s] = 1
            self._succ_matrix = jnp.asarray(succ)
        self._state_vars = [
            v
            for v in sorted(lowered.var_specs)
            if v not in lowered.temp_vars
        ]
        # Static count of masked whole-state top updates per dispatch of
        # each block: one per LPrim output that lands in VM state plus one
        # per push/pop top write.  Multiplied by block_exec post-run to
        # give SchedulerStats.masked_updates (the metric layout packing
        # cuts: packed members become temps, so a block writes the one
        # grouped array instead of one masked top per member).
        self._masked_writes = [
            sum(
                len([o for o in op.outs if o not in lowered.temp_vars])
                if isinstance(op, ir.LPrim)
                else 1
                for op in blk.ops
            )
            for blk in lowered.blocks
        ]
        self._block_fns = [
            self._make_block_fn(i, blk) for i, blk in enumerate(lowered.blocks)
        ]
        # tag -> [(block_idx, multiplicity)] for post-run instrumentation.
        self._tag_blocks: dict[str, list[tuple[int, int]]] = {}
        for i, blk in enumerate(lowered.blocks):
            for op in blk.ops:
                if isinstance(op, ir.LPrim) and op.tag:
                    entry = self._tag_blocks.setdefault(op.tag, [])
                    entry.append((i, 1))
        # One-program path (kept for .lower()/cost_analysis), plus a
        # two-stage path for run(): init and loop are jitted separately so
        # the loop-carried state pytree can be donated — steady-state
        # memory stays flat at one copy of the VM state.
        self._jitted = jax.jit(self._run)
        self._jitted_start = jax.jit(self._start)
        self._jitted_loop = jax.jit(self._loop, donate_argnums=(0,))
        # Segmented-execution entry points.  All take the state snapshot
        # first and donate it, so a resumable run is as memory-flat as a
        # single-shot one.
        self._jitted_segment = jax.jit(self._segment, donate_argnums=(0,))
        self._jitted_inject = jax.jit(self._inject, donate_argnums=(0,))
        self._jitted_park = jax.jit(self._park, donate_argnums=(0,))

    # ------------------------------------------------------------------
    # State construction
    # ------------------------------------------------------------------

    def _layout_slot(self, v: str) -> Optional[tuple[str, int]]:
        """``(packed_var, slot)`` when ``v`` lives in a packed layout group
        (see ``ir.StateLayout``), else None."""
        layout = self.lowered.state_layout
        return None if layout is None else layout.slot_of(v)

    def read_top(self, state: dict[str, Any], v: str) -> Array:
        """Current ``[batch, ...]`` value of a cross-block variable, in row
        order.  Layout-transparent: a packed member is sliced out of its
        grouped array, so inject/park/outputs/Stepper callers never see the
        packed layout.  (Use :meth:`unpermute` for caller lane order.)
        """
        slot = self._layout_slot(v)
        if slot is None:
            return state["tops"][v]
        packed, idx = slot
        return state["tops"][packed][:, idx]

    def init_state(self, inputs: dict[str, Array]) -> dict[str, Any]:
        cfg = self.config
        z, d = cfg.batch_size, cfg.max_depth
        lp = self.lowered
        tops: dict[str, Array] = {}
        stacks: dict[str, Array] = {}
        ptrs: dict[str, Array] = {}
        for v in self._state_vars:
            spec = lp.var_specs[v]
            tops[v] = jnp.zeros((z,) + tuple(spec.shape), spec.dtype)
            if v in lp.stack_vars:
                stacks[v] = jnp.zeros((d, z) + tuple(spec.shape), spec.dtype)
                ptrs[v] = jnp.zeros((z,), _I32)
        for p in lp.main_params:
            x = jnp.asarray(inputs[p])
            if x.shape != (z,) + tuple(lp.var_specs[p].shape):
                raise ValueError(
                    f"input {p!r}: expected batched shape "
                    f"{(z,) + tuple(lp.var_specs[p].shape)}, got {x.shape}"
                )
            x = x.astype(lp.var_specs[p].dtype)
            slot = self._layout_slot(p)
            if slot is None:
                tops[p] = x
            else:
                # Packed-layout member: the param's cross-block home is a
                # slot of the grouped array (the member itself is a temp).
                packed, idx = slot
                tops[packed] = tops[packed].at[:, idx].set(x)
        pc_stack = jnp.full((d, z), lp.exit_index, _I32)
        state = {
            "pc_top": jnp.full((z,), lp.entry, _I32),
            "pc_stack": pc_stack,  # slot 0 holds the exit sentinel
            "pc_ptr": jnp.ones((z,), _I32),
            "tops": tops,
            "stacks": stacks,
            "ptrs": ptrs,
            "steps": jnp.zeros((), _I32),
            # Per-member overflow flag: set when a push would land at or
            # beyond max_depth (the scatter drops it, invalidating that
            # member's results).
            "depth_exceeded": jnp.zeros((z,), jnp.bool_),
            # Per-lane fault code (FAULT_*); first fault wins, inject clears.
            "fault_code": jnp.zeros((z,), _I32),
            # Per-lane count of block dispatches the lane was active in —
            # the watchdog's clock, and cheap per-lane progress telemetry.
            "lane_steps": jnp.zeros((z,), _I32),
        }
        if cfg.compact_every is not None:
            # Which ORIGINAL lane each row currently holds.  Compaction
            # permutes rows; every identity-bearing surface inverts this
            # to restore caller lane order.  Only materialized when
            # compaction is on, so the uncompacted VM carries no overhead.
            state["lane_ids"] = jnp.arange(z, dtype=_I32)
        if self.config.collect_block_stats:
            state["block_exec"] = jnp.zeros((self.num_blocks,), _I32)
            state["block_active"] = jnp.zeros((self.num_blocks,), _I32)
            # Occupied-tile capacity accumulated over dispatches — the
            # denominator of the tile-based mean_occupancy.
            state["tile_acc"] = jnp.zeros((), _I32)
        if self.trace_capacity is not None:
            # Dispatch-trace ring buffers: one event per loop iteration at
            # index steps % capacity (so `steps` doubles as the event
            # count and the drain never needs a separate cursor).  All
            # write-only w.r.t. execution — see the module docstring.
            c = self.trace_capacity
            state["trace"] = {
                "block": jnp.full((c,), -1, _I32),
                "resident": jnp.zeros((c, self.num_blocks), _I32),
                "active": jnp.zeros((c,), _I32),
                "live": jnp.zeros((c,), _I32),
                "quarantined": jnp.zeros((c,), _I32),
                "tile": jnp.zeros((c,), _I32),
                "compacted": jnp.zeros((c,), jnp.bool_),
                "faults": jnp.zeros((c,), _I32),
            }
        return state

    def _shard_state(self, state: dict[str, Any]) -> dict[str, Any]:
        """Pin the lane layout of every state array (no-op without a mesh).

        Lane-major arrays (``[batch, ...]`` tops/pointers/masks) shard their
        leading axis over :data:`LANE_AXIS`; ``[depth, batch, ...]`` stacks
        shard axis 1; scalars and the ``[num_blocks]`` stat counters are
        replicated.  Constraining the initial carry is enough — GSPMD
        propagates the layout through the whole ``lax.while_loop``.
        """
        if self.mesh is None:
            return state
        wsc = jax.lax.with_sharding_constraint
        lane, stack, repl = (
            self._lane_sharding, self._stack_sharding, self._replicated
        )
        out = dict(state)
        out["pc_top"] = wsc(state["pc_top"], lane)
        out["pc_stack"] = wsc(state["pc_stack"], stack)
        out["pc_ptr"] = wsc(state["pc_ptr"], lane)
        out["depth_exceeded"] = wsc(state["depth_exceeded"], lane)
        out["fault_code"] = wsc(state["fault_code"], lane)
        out["lane_steps"] = wsc(state["lane_steps"], lane)
        out["tops"] = {v: wsc(x, lane) for v, x in state["tops"].items()}
        out["stacks"] = {v: wsc(x, stack) for v, x in state["stacks"].items()}
        out["ptrs"] = {v: wsc(x, lane) for v, x in state["ptrs"].items()}
        out["steps"] = wsc(state["steps"], repl)
        if "lane_ids" in state:
            out["lane_ids"] = wsc(state["lane_ids"], lane)
        if "block_exec" in state:
            out["block_exec"] = wsc(state["block_exec"], repl)
            out["block_active"] = wsc(state["block_active"], repl)
            out["tile_acc"] = wsc(state["tile_acc"], repl)
        if "trace" in state:
            # Trace rings are event-major (not lane-major): replicate.
            out["trace"] = {
                k: wsc(x, repl) for k, x in state["trace"].items()
            }
        return out

    # ------------------------------------------------------------------
    # Block body compilation
    # ------------------------------------------------------------------

    def _make_block_fn(self, bidx: int, blk: ir.LBlock) -> Callable:
        lowered = self.lowered
        temp_vars = lowered.temp_vars
        use_kernel = self.config.use_kernel
        max_depth = self.config.max_depth
        quarantine = self.config.on_fault == "quarantine"
        detect_nonfinite = self.config.detect_nonfinite
        budget = self.config.lane_step_budget
        exit_idx = lowered.exit_index
        # Bound in __init__: plain Pallas wrappers, or shard-local (per
        # device lane slice via shard_map) versions when a mesh is set.
        kernel_push, kernel_peek = self._kernel_push, self._kernel_peek

        def run(state: dict[str, Any]) -> dict[str, Any]:
            mask = state["pc_top"] == bidx
            fault_code = state["fault_code"]
            if quarantine:
                # Quarantined lanes never dispatch again: every masked
                # update below sees them as inactive, freezing their state.
                mask = jnp.logical_and(mask, fault_code == FAULT_OK)
            imask = mask.astype(_I32)
            tops = dict(state["tops"])
            stacks = dict(state["stacks"])
            ptrs = dict(state["ptrs"])
            depth_exceeded = state["depth_exceeded"]
            temps: dict[str, Array] = {}

            def set_fault(where: Array, code: int) -> None:
                # First fault wins: only OK lanes take a new code.
                nonlocal fault_code
                fault_code = jnp.where(
                    jnp.logical_and(where, fault_code == FAULT_OK),
                    jnp.asarray(code, _I32),
                    fault_code,
                )

            def check_finite(val: Array) -> None:
                # Opt-in NONFINITE detection on values entering VM state.
                if not jnp.issubdtype(val.dtype, jnp.inexact):
                    return
                bad = jnp.logical_not(jnp.isfinite(val))
                if val.ndim > 1:
                    bad = jnp.any(bad, axis=tuple(range(1, val.ndim)))
                set_fault(jnp.logical_and(mask, bad), FAULT_NONFINITE)

            def read(v: str) -> Array:
                return temps[v] if v in temp_vars else tops[v]

            def write(v: str, val: Array) -> None:
                if v in temp_vars:
                    temps[v] = val
                else:
                    if detect_nonfinite:
                        check_finite(val)
                    tops[v] = _masked(mask, val.astype(tops[v].dtype), tops[v])

            for op in blk.ops:
                if isinstance(op, ir.LPrim):
                    if not op.ins and not op.batched:
                        # Nullary primitive (constant): broadcast to the batch.
                        z = mask.shape[0]
                        outs = op.fn()
                        outs = outs if isinstance(outs, tuple) else (outs,)
                        outs = tuple(
                            jnp.broadcast_to(
                                jnp.asarray(o), (z,) + jnp.shape(jnp.asarray(o))
                            )
                            for o in outs
                        )
                    else:
                        fn = op.fn if op.batched else jax.vmap(op.fn)
                        outs = fn(*[read(i) for i in op.ins])
                        if len(op.outs) == 1:
                            outs = (outs,)
                    for name, val in zip(op.outs, outs):
                        write(name, val)
                elif isinstance(op, ir.LPush):
                    old_top = tops[op.var]
                    overflow = jnp.logical_and(
                        mask, ptrs[op.var] >= max_depth
                    )
                    depth_exceeded = jnp.logical_or(depth_exceeded, overflow)
                    set_fault(overflow, FAULT_STACK_OVERFLOW)
                    if use_kernel:
                        stacks[op.var] = kernel_push(
                            stacks[op.var], ptrs[op.var], old_top, mask
                        )
                    else:
                        stacks[op.var] = _scatter_push(
                            stacks[op.var], ptrs[op.var], old_top, mask
                        )
                    ptrs[op.var] = ptrs[op.var] + imask
                    new_top = read(op.src)
                    if detect_nonfinite:
                        check_finite(new_top)
                    tops[op.var] = _masked(mask, new_top, old_top)
                elif isinstance(op, ir.LPop):
                    new_ptr = ptrs[op.var] - imask
                    if use_kernel:
                        restored = kernel_peek(stacks[op.var], new_ptr)
                    else:
                        restored = _gather_top(stacks[op.var], new_ptr)
                    tops[op.var] = _masked(mask, restored, tops[op.var])
                    ptrs[op.var] = new_ptr
                else:  # pragma: no cover
                    raise AssertionError(op)

            pc_top = state["pc_top"]
            pc_stack = state["pc_stack"]
            pc_ptr = state["pc_ptr"]
            t = blk.term
            if isinstance(t, ir.LJump):
                pc_top = jnp.where(mask, t.target, pc_top)
            elif isinstance(t, ir.LBranch):
                cond = read(t.var)
                pc_top = jnp.where(
                    mask, jnp.where(cond, t.true, t.false), pc_top
                )
            elif isinstance(t, ir.LPushJump):
                # Bury the return address; jump to the callee entry.
                ret = jnp.full_like(pc_top, t.ret)
                pc_overflow = jnp.logical_and(mask, pc_ptr >= max_depth)
                depth_exceeded = jnp.logical_or(depth_exceeded, pc_overflow)
                set_fault(pc_overflow, FAULT_STACK_OVERFLOW)
                pc_stack = _scatter_push(pc_stack, pc_ptr, ret, mask)
                pc_ptr = pc_ptr + imask
                pc_top = jnp.where(mask, t.target, pc_top)
            elif isinstance(t, ir.LReturn):
                new_ptr = pc_ptr - imask
                restored = _gather_top(pc_stack, new_ptr)
                pc_top = jnp.where(mask, restored, pc_top)
                pc_ptr = new_ptr
            else:  # pragma: no cover
                raise AssertionError(t)

            # Watchdog: lanes pay one tick per dispatch they were active
            # in; a lane that burns its budget without halting is faulted.
            lane_steps = state["lane_steps"] + imask
            if budget is not None:
                set_fault(
                    jnp.logical_and(
                        jnp.logical_and(mask, lane_steps >= budget),
                        pc_top < exit_idx,
                    ),
                    FAULT_WATCHDOG,
                )

            out = dict(state)
            out.update(
                pc_top=pc_top,
                pc_stack=pc_stack,
                pc_ptr=pc_ptr,
                tops=tops,
                stacks=stacks,
                ptrs=ptrs,
                depth_exceeded=depth_exceeded,
                fault_code=fault_code,
                lane_steps=lane_steps,
            )
            return out

        def scoped_run(state: dict[str, Any]) -> dict[str, Any]:
            # Label the block body in the HLO metadata so device profiles
            # (jax.profiler / XProf) line up with DispatchTrace events by
            # block id.  Pure metadata — numerics and scheduling are
            # untouched.
            with jax.named_scope(f"pcvm.block{bidx}"):
                return run(state)

        return scoped_run

    # ------------------------------------------------------------------
    # The VM loop
    # ------------------------------------------------------------------

    def _pick_block(self, state: dict[str, Any]) -> Array:
        """The schedule's block choice for one dispatch (traced).

        With a mesh this is one of the two global reductions in the whole
        program (the other is liveness in ``cond``): a min/argmax over the
        per-lane pc values that all-reduces ONE i32 scalar per iteration —
        there is deliberately no lane-shaped cross-device traffic here.
        """
        exit_idx = self.lowered.exit_index
        pc_top = state["pc_top"]
        live = self._live_mask(state)
        schedule = self.config.schedule
        if schedule in ("popular", "lookahead"):
            # Occupancy argmax: the block where most live members reside.
            # The [num_blocks] histogram is replicated; the scatter-add over
            # lanes reduces to a per-block integer sum (associative, so the
            # result is identical however lanes are placed).
            counts = (
                jnp.zeros((self.num_blocks,), _I32)
                .at[jnp.where(live, pc_top, self.num_blocks)]
                .add(1, mode="drop")
            )
            if schedule == "popular":
                return jnp.argmax(counts).astype(_I32)
            # Lookahead: own residents count double, plus the residents of
            # the block's CFG successors — a populated block that feeds
            # other populated blocks re-converges the batch fastest.  Only
            # resident blocks are eligible (score -1 keeps empty blocks
            # out); integer arithmetic on a replicated [B] vector, so the
            # pick is deterministic and placement-independent.
            score = 2 * counts + self._succ_matrix @ counts
            score = jnp.where(counts > 0, score, -1)
            return jnp.argmax(score).astype(_I32)
        # Earliest-block heuristic (Algorithm 1/2's block choice).
        return jnp.min(jnp.where(live, pc_top, exit_idx)).astype(_I32)

    def _start(self, inputs: dict[str, Array]) -> dict[str, Any]:
        """Inputs -> initial VM state, with the lane layout pinned."""
        return self._shard_state(self.init_state(inputs))

    def _run(self, inputs: dict[str, Array]) -> dict[str, Any]:
        return self._loop(self._start(inputs))

    def _live_mask(self, state: dict[str, Any]) -> Array:
        """[batch] bool: lanes that still dispatch.  Under quarantine a
        faulted lane is no longer live, whatever its pc says."""
        live = state["pc_top"] < self.lowered.exit_index
        if self.config.on_fault == "quarantine":
            live = jnp.logical_and(live, state["fault_code"] == FAULT_OK)
        return live

    def _liveness_cond(self, state: dict[str, Any]) -> Array:
        # Global liveness: ``any`` over the lane axis — a single bool
        # all-reduce per iteration under a mesh.
        cond = jnp.logical_and(
            state["steps"] < self.config.max_steps,
            jnp.any(self._live_mask(state)),
        )
        if self.config.on_fault == "raise" and (
            self.config.detect_nonfinite
            or self.config.lane_step_budget is not None
        ):
            # Fail fast: a NONFINITE/WATCHDOG fault is batch-fatal under
            # "raise", so stop the loop instead of spinning to max_steps
            # (a livelocked lane would otherwise never let cond go false).
            cond = jnp.logical_and(
                cond,
                jnp.logical_not(
                    jnp.any(state["fault_code"] >= FAULT_NONFINITE)
                ),
            )
        return cond

    def _trace_event(
        self, state: dict[str, Any], block: Any, dispatch_mask: Array
    ) -> dict[str, Array]:
        """Pre-dispatch snapshot of one trace event (traced scalars).

        Everything here is *derived* from the state the scheduler already
        read — the histogram is the same scatter-add ``_pick_block`` uses
        and the counts are the same integer all-reduces the stats path
        performs — so recording cannot perturb execution.
        """
        live = self._live_mask(state)
        counts = (
            jnp.zeros((self.num_blocks,), _I32)
            .at[jnp.where(live, state["pc_top"], self.num_blocks)]
            .add(1, mode="drop")
        )
        return {
            # Pre-increment steps == this dispatch's global ordinal ==
            # its ring slot (idx = step % capacity).
            "step": state["steps"],
            "block": jnp.asarray(block, _I32),
            "resident": counts,
            "active": jnp.sum(dispatch_mask.astype(_I32)),
            "live": jnp.sum(live.astype(_I32)),
            "quarantined": jnp.sum(
                (state["fault_code"] != FAULT_OK).astype(_I32)
            ),
            "tile": _tile_capacity(dispatch_mask),
        }

    def _trace_commit(
        self, state: dict[str, Any], ev: dict[str, Array]
    ) -> dict[str, Any]:
        """Write one event into the ring (post-dispatch, steps bumped).

        The fault count is read *after* the dispatch so the event shows
        faults the dispatch itself caused; the compaction flag mirrors
        ``_maybe_compact``'s cadence condition exactly.
        """
        idx = ev["step"] % self.trace_capacity
        k = self.config.compact_every
        compacted = (
            jnp.asarray(False)
            if k is None
            else (state["steps"] % k) == 0  # post-increment, == _maybe_compact
        )
        faults = jnp.sum((state["fault_code"] != FAULT_OK).astype(_I32))
        tb = dict(state["trace"])
        tb["block"] = tb["block"].at[idx].set(ev["block"])
        tb["resident"] = tb["resident"].at[idx].set(ev["resident"])
        tb["active"] = tb["active"].at[idx].set(ev["active"])
        tb["live"] = tb["live"].at[idx].set(ev["live"])
        tb["quarantined"] = tb["quarantined"].at[idx].set(ev["quarantined"])
        tb["tile"] = tb["tile"].at[idx].set(ev["tile"])
        tb["compacted"] = tb["compacted"].at[idx].set(compacted)
        tb["faults"] = tb["faults"].at[idx].set(faults)
        out = dict(state)
        out["trace"] = tb
        return out

    def _make_body(self) -> Callable:
        """The loop body for this config's schedule (shared by the
        single-shot and segmented loops, so the two are bit-exact)."""
        collect = self.config.collect_block_stats
        tracing = self.trace_capacity is not None
        quarantine = self.config.on_fault == "quarantine"

        def resident(state, b):
            # The same mask the block body dispatches under — quarantined
            # lanes don't count toward occupancy.
            m = state["pc_top"] == b
            if quarantine:
                m = jnp.logical_and(m, state["fault_code"] == FAULT_OK)
            return m

        def body_switch(state):
            i = self._pick_block(state)
            if collect:
                m = resident(state, i)
                active = jnp.sum(m.astype(_I32))
                state = dict(state)
                state["block_exec"] = state["block_exec"].at[i].add(1)
                state["block_active"] = state["block_active"].at[i].add(active)
                state["tile_acc"] = state["tile_acc"] + _tile_capacity(m)
            ev = self._trace_event(state, i, resident(state, i)) if tracing \
                else None
            state = lax.switch(i, self._block_fns, state)
            state = dict(state)
            state["steps"] = state["steps"] + 1
            if tracing:
                state = self._trace_commit(state, ev)
            return self._maybe_compact(state)

        def body_sweep(state):
            # One trace event per sweep iteration: there is no single
            # chosen block (block = -1, obs.trace.SWEEP_BLOCK) and every
            # live lane is dispatchable, so active/tile cover the live set.
            ev = (
                self._trace_event(state, -1, self._live_mask(state))
                if tracing else None
            )
            # Run every resident block once, in index order, each under its
            # own mask — no lax.switch at all.  A member can traverse
            # several (forward) blocks within one sweep.
            for b, fn in enumerate(self._block_fns):
                if collect:
                    m = resident(state, b)
                    active = jnp.sum(m.astype(_I32))
                    state = dict(state)
                    # Count a dispatch only when it had resident members,
                    # so utilization stays comparable across schedules.
                    state["block_exec"] = (
                        state["block_exec"].at[b].add((active > 0).astype(_I32))
                    )
                    state["block_active"] = (
                        state["block_active"].at[b].add(active)
                    )
                    state["tile_acc"] = state["tile_acc"] + jnp.where(
                        active > 0, _tile_capacity(m), 0
                    )
                state = fn(state)
            state = dict(state)
            state["steps"] = state["steps"] + 1
            if tracing:
                state = self._trace_commit(state, ev)
            return self._maybe_compact(state)

        return body_sweep if self.config.schedule == "sweep" else body_switch

    # ------------------------------------------------------------------
    # Occupancy-aware lane compaction
    # ------------------------------------------------------------------

    def _compact(self, state: dict[str, Any]) -> dict[str, Any]:
        """Permute the lane axis so same-pc live lanes are contiguous.

        Stable argsort on ``(liveness, pc_top)``: live lanes group by
        program point in block order, exited/quarantined lanes sink to the
        high end.  Every lane-major array moves by the same permutation
        and ``lane_ids`` records it, so per-lane semantics are untouched —
        only the SIMD tile layout changes.  Schedules read lane state
        through permutation-invariant reductions (histogram / min / any),
        so the dispatch sequence is bit-exact with the uncompacted run.
        """
        live = self._live_mask(state)
        key = jnp.where(
            live, state["pc_top"], jnp.asarray(self.num_blocks + 1, _I32)
        )
        perm = jnp.argsort(key, stable=True)

        def take(x):  # [batch, ...] arrays
            return jnp.take(x, perm, axis=0)

        def take1(x):  # [depth, batch, ...] stacks
            return jnp.take(x, perm, axis=1)

        out = dict(state)
        for k in (
            "pc_top", "pc_ptr", "depth_exceeded",
            "fault_code", "lane_steps", "lane_ids",
        ):
            out[k] = take(state[k])
        out["pc_stack"] = take1(state["pc_stack"])
        out["tops"] = {v: take(x) for v, x in state["tops"].items()}
        out["stacks"] = {v: take1(x) for v, x in state["stacks"].items()}
        out["ptrs"] = {v: take(x) for v, x in state["ptrs"].items()}
        return self._shard_state(out)

    def _maybe_compact(self, state: dict[str, Any]) -> dict[str, Any]:
        """Compaction hook at the end of every loop body iteration."""
        k = self.config.compact_every
        if k is None:
            return state
        if k == 1:
            return self._compact(state)
        # ``steps`` was just incremented, so the first compaction lands
        # after dispatch k — a traced-counter condition, shared by the
        # single-shot and segmented loops (steps is global), so segment
        # boundaries never change where compaction happens.
        return lax.cond(
            state["steps"] % k == 0,
            self._compact,
            lambda s: self._shard_state(dict(s)),
            state,
        )

    def _lane_restore(self, state: dict[str, Any]) -> Optional[Array]:
        """Inverse lane permutation (row -> original order), or None when
        compaction is off and rows already are in caller order."""
        if self.config.compact_every is None:
            return None
        return jnp.argsort(state["lane_ids"])

    def unpermute(self, state: dict[str, Any], x: Array) -> Array:
        """View a row-order ``[batch, ...]`` array in original lane order.

        Identity when compaction is off.  Every public per-lane surface
        (results, halt/fault flags, Stepper views) goes through this, so
        callers never observe the compaction permutation.
        """
        inv = self._lane_restore(state)
        return x if inv is None else jnp.take(x, inv, axis=0)

    def _lane_select(self, state: dict[str, Any], x: Array) -> Array:
        """Translate an original-lane-order ``[batch, ...]`` array (an
        inject/park mask or fresh inputs) into current row order."""
        if self.config.compact_every is None:
            return x
        return jnp.take(x, state["lane_ids"], axis=0)

    def _loop(self, state: dict[str, Any]) -> dict[str, Any]:
        return lax.while_loop(self._liveness_cond, self._make_body(), state)

    def _segment(self, state: dict[str, Any], num_steps: Array) -> dict[str, Any]:
        """At most ``num_steps`` more loop iterations from ``state``.

        ``num_steps`` is a traced i32 scalar, so every segment size shares
        one compiled executable.  The body is the exact function the
        single-shot loop runs; only the ``cond`` gains the extra bound
        (``steps`` is part of the carry, so the bound composes with
        ``max_steps`` exactly as a single shot would observe it).
        """
        limit = jnp.minimum(
            state["steps"] + jnp.asarray(num_steps, _I32),
            jnp.asarray(self.config.max_steps, _I32),
        )

        def cond(st):
            return jnp.logical_and(
                st["steps"] < limit, self._liveness_cond(st)
            )

        return lax.while_loop(cond, self._make_body(), state)

    def run(self, inputs: dict[str, Array]) -> VMResult:
        """Execute the batched program to completion.

        Runs two jitted stages — state construction, then the while loop
        with the state pytree donated into it — so a run never holds more
        than one copy of the VM state.
        """
        # Host-side profiler annotation: a jax.profiler trace of the
        # caller shows VM runs as named spans that device profiles (and
        # DispatchTrace timelines) can be lined up against.
        with jax.profiler.TraceAnnotation("pcvm.run"):
            state = self._jitted_start(inputs)
            state = self._jitted_loop(state)
            return self._result(state)

    # ------------------------------------------------------------------
    # Segmented (resumable) execution
    # ------------------------------------------------------------------

    def start(self, inputs: dict[str, Array]) -> dict[str, Any]:
        """Inputs -> an initial state snapshot (lane layout pinned).

        The snapshot is an ordinary pytree of arrays; hold it on the host,
        checkpoint it, or feed it straight back into :meth:`run_segment`.
        """
        return self._jitted_start(inputs)

    def run_segment(
        self, state: dict[str, Any], num_steps: int
    ) -> dict[str, Any]:
        """Advance a snapshot by at most ``num_steps`` loop iterations.

        Returns the updated snapshot (the input snapshot is donated — do
        not reuse it).  A chain of segments of any sizes is bit-exact with
        a single :meth:`run`: the segment loop runs the identical body and
        the snapshot carries every piece of execution state.  ``num_steps`` counts loop iterations — single
        block dispatches for ``earliest``/``popular``, whole sweeps for
        ``sweep`` — matching the ``steps`` counter.
        """
        # Segment boundaries show up as named spans in jax.profiler
        # traces, so host-loop overhead (admit/retire between segments)
        # is separable from VM time.
        with jax.profiler.TraceAnnotation("pcvm.run_segment"):
            return self._jitted_segment(state, jnp.asarray(num_steps, _I32))

    def lane_done(self, state: dict[str, Any]) -> Array:
        """Per-lane halt flags: ``[batch]`` bool, True once a lane exited.

        Like every per-lane surface, reported in original (caller) lane
        order regardless of ``compact_every``."""
        return self.unpermute(
            state, state["pc_top"] >= self.lowered.exit_index
        )

    def lane_fault(self, state: dict[str, Any]) -> Array:
        """Per-lane fault codes: ``[batch]`` i32 (see :data:`FAULT_NAMES`)."""
        return self.unpermute(state, state["fault_code"])

    def lane_faulted(self, state: dict[str, Any]) -> Array:
        """Per-lane fault flags: ``[batch]`` bool, True once a lane faulted."""
        return self.unpermute(state, state["fault_code"] != FAULT_OK)

    def lane_depth_exceeded(self, state: dict[str, Any]) -> Array:
        """Per-lane overflow flags, original lane order: ``[batch]`` bool."""
        return self.unpermute(state, state["depth_exceeded"])

    def park(self, state: dict[str, Any], mask: Array) -> dict[str, Any]:
        """Force masked lanes to the exit block (idle, excluded from
        liveness).  Used to hold lanes that have no work assigned yet."""
        return self._jitted_park(state, jnp.asarray(mask, jnp.bool_))

    def inject(
        self, state: dict[str, Any], mask: Array, inputs: dict[str, Array]
    ) -> dict[str, Any]:
        """Re-initialize the masked lanes with fresh program inputs.

        For lanes where ``mask`` is True this is exactly ``init_state``:
        pc reset to the entry block, pc/variable stacks and pointers
        cleared, overflow flags cleared, non-parameter tops zeroed, and
        parameter tops loaded from ``inputs`` (full ``[batch, ...]``
        arrays; unmasked rows are ignored).  Unmasked lanes — and the
        global step/occupancy counters — are untouched, so in-flight work
        keeps running.  This is the refill half of retire-and-refill.
        """
        cfg = self.config
        lp = self.lowered
        z = cfg.batch_size
        fresh: dict[str, Array] = {}
        for p in lp.main_params:
            x = jnp.asarray(inputs[p])
            if x.shape != (z,) + tuple(lp.var_specs[p].shape):
                raise ValueError(
                    f"inject input {p!r}: expected batched shape "
                    f"{(z,) + tuple(lp.var_specs[p].shape)}, got {x.shape}"
                )
            fresh[p] = x.astype(lp.var_specs[p].dtype)
        return self._jitted_inject(state, jnp.asarray(mask, jnp.bool_), fresh)

    def _park(self, state: dict[str, Any], mask: Array) -> dict[str, Any]:
        mask = self._lane_select(state, mask)  # caller order -> row order
        out = dict(state)
        out["pc_top"] = jnp.where(
            mask, jnp.asarray(self.lowered.exit_index, _I32), state["pc_top"]
        )
        return self._shard_state(out)

    def _inject(
        self,
        state: dict[str, Any],
        mask: Array,
        fresh: dict[str, Array],
    ) -> dict[str, Any]:
        lp = self.lowered
        # Callers address lanes by original identity; rows may be permuted.
        mask = self._lane_select(state, mask)
        fresh = {p: self._lane_select(state, x) for p, x in fresh.items()}

        def col_masked(new, old):
            # [depth, batch, ...] arrays: mask selects whole lane columns.
            m = mask.reshape((1,) + mask.shape + (1,) * (old.ndim - 2))
            return jnp.where(m, new, old)

        out = dict(state)
        out["pc_top"] = jnp.where(
            mask, jnp.asarray(lp.entry, _I32), state["pc_top"]
        )
        out["pc_ptr"] = jnp.where(mask, 1, state["pc_ptr"])
        out["pc_stack"] = col_masked(
            jnp.asarray(lp.exit_index, _I32), state["pc_stack"]
        )
        out["depth_exceeded"] = jnp.logical_and(
            state["depth_exceeded"], jnp.logical_not(mask)
        )
        # A refilled lane starts healthy: fault code and watchdog clock
        # reset with the rest of its state.
        out["fault_code"] = jnp.where(mask, FAULT_OK, state["fault_code"])
        out["lane_steps"] = jnp.where(mask, 0, state["lane_steps"])
        tops = dict(state["tops"])
        for v in self._state_vars:
            tops[v] = _masked(mask, jnp.zeros_like(tops[v]), tops[v])
        for p in lp.main_params:
            slot = self._layout_slot(p)
            if slot is None:
                tops[p] = _masked(mask, fresh[p], tops[p])
            else:
                # Packed-layout member: masked write into the param's slot
                # of the grouped array (already zeroed above with the rest
                # of VM state).
                packed, idx = slot
                tops[packed] = tops[packed].at[:, idx].set(
                    _masked(mask, fresh[p], tops[packed][:, idx])
                )
        out["tops"] = tops
        out["stacks"] = {
            v: col_masked(jnp.zeros_like(s), s)
            for v, s in state["stacks"].items()
        }
        out["ptrs"] = {
            v: jnp.where(mask, 0, p) for v, p in state["ptrs"].items()
        }
        return self._shard_state(out)

    def result(self, state: dict[str, Any]) -> VMResult:
        """Materialize a :class:`VMResult` from a state snapshot.

        Valid on any snapshot; ``converged`` reports whether *all* lanes
        have halted (partial snapshots simply report in-flight tops)."""
        return self._result(state)

    def get_trace(self, state: dict[str, Any]):
        """Drain the dispatch-trace ring buffer from a state snapshot.

        Returns a :class:`repro.obs.trace.DispatchTrace` (host numpy,
        oldest surviving event first), or ``None`` when the VM was built
        without ``trace=``.  Valid on any snapshot — mid-run, between
        :meth:`run_segment` calls, or after completion; draining syncs
        the device (it reads the buffers) but does not consume them, so
        a later drain sees the same events plus any new ones.
        """
        if self.trace_capacity is None:
            return None
        from repro.obs.trace import drain

        buffers = jax.device_get(state["trace"])
        total = int(jax.device_get(state["steps"]))
        return drain(
            buffers,
            total=total,
            schedule=self.config.schedule,
            num_blocks=self.num_blocks,
            batch_size=self.config.batch_size,
        )

    def _result(self, state) -> VMResult:
        lp = self.lowered
        # Restore caller lane order on every per-lane array (identity when
        # compaction is off) — compaction must be invisible in results.
        inv = self._lane_restore(state)

        def restore(x):
            return x if (x is None or inv is None) else jnp.take(x, inv, 0)

        outputs = {o: restore(self.read_top(state, o)) for o in lp.main_outputs}
        done = state["pc_top"] >= lp.exit_index
        if self.config.on_fault == "quarantine":
            # A quarantined lane will never reach the exit block; the run
            # still converged if every lane either halted or faulted.
            done = jnp.logical_or(done, state["fault_code"] != FAULT_OK)
        converged = jnp.all(done)
        block_exec = state.get("block_exec")
        block_active = state.get("block_active")
        tag_stats: dict[str, tuple[int, int]] = {}
        mean_occ = float("nan")
        mean_lane_occ = float("nan")
        steps = None
        masked_updates = None
        if block_exec is not None:
            be = jax.device_get(block_exec)
            ba = jax.device_get(block_active)
            for tag, entries in self._tag_blocks.items():
                execs = sum(int(be[b]) * m for b, m in entries)
                active = sum(int(ba[b]) * m for b, m in entries)
                tag_stats[tag] = (execs, active)
            dispatches = int(be.sum())
            tile_cap = int(jax.device_get(state["tile_acc"]))
            if dispatches:
                # Tile-based SIMD occupancy: actives / occupied-tile
                # capacity (see OCCUPANCY_TILE).  The legacy whole-batch
                # ratio rides along for trajectory comparisons.
                mean_lane_occ = float(ba.sum()) / (
                    dispatches * self.config.batch_size
                )
            if tile_cap:
                mean_occ = float(ba.sum()) / tile_cap
            steps = int(jax.device_get(state["steps"]))
            masked_updates = sum(
                int(be[b]) * w for b, w in enumerate(self._masked_writes)
            )
        sched = SchedulerStats(
            schedule=self.config.schedule,
            fused=lp.fused_from is not None,
            num_blocks=self.num_blocks,
            steps=steps,
            mean_occupancy=mean_occ,
            fused_from=lp.fused_from,
            num_devices=self.mesh.size if self.mesh is not None else 1,
            mean_lane_occupancy=mean_lane_occ,
            compact_every=self.config.compact_every,
            masked_updates=masked_updates,
        )
        return VMResult(
            outputs=outputs,
            steps=state["steps"],
            converged=converged,
            block_exec=block_exec,
            block_active=block_active,
            tag_stats=tag_stats,
            depth_exceeded=restore(state.get("depth_exceeded")),
            sched=sched,
            fault_code=restore(state.get("fault_code")),
            lane_steps=restore(state.get("lane_steps")),
            # Tracing syncs here (the drain reads the device buffers) —
            # like collect_block_stats, enabling it trades result-time
            # asynchrony for observability.
            trace=self.get_trace(state),
        )

    # ------------------------------------------------------------------
    # AOT entry points (for dry-runs and benchmarking)
    # ------------------------------------------------------------------

    def lower(self, inputs: dict[str, Array]):
        return self._jitted.lower(inputs)

    def step_fn(self) -> Callable:
        """One VM step as a standalone jittable function of the state.

        Honors ``config.schedule``: a single scheduled dispatch for
        ``earliest``/``popular``, a full masked pass over every block for
        ``sweep``.
        """

        def step(state):
            if self.config.schedule == "sweep":
                for fn in self._block_fns:
                    state = fn(state)
                return state
            i = self._pick_block(state)
            return lax.switch(i, self._block_fns, state)

        return step
