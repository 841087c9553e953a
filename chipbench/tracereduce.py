"""Reduce a profiler trace to the numbers the per-layer metrics read.

Three parts, all on the device's own timeline:

* busy time: the union of the intervals in which a device operation ran.
  Control-flow operations (``while``, ``conditional``, ``call``) only
  contain other operations, so they are left out of the union: the time
  between two operations inside a loop is idle time;
* time per scope: the union of the operations whose op name (the
  ``tf_op`` of the trace, i.e. JAX's name stack) contains a scope name,
  such as ``bench.logp`` or the program's ``pcvm.block`` prefix;
* idle gaps: the holes in the busy union inside the traced window, each
  labelled by what the host was doing at its middle: inside the
  program's ``pcvm.run`` span, inside the harness's ``bench.call`` span
  but not the program's, or in the harness between calls;
* the spans of each XLA program (module) that ran, so that a metric can
  find the program that holds a scope's operations, such as the VM's
  loop, by the scope and not by the program's name.

The window runs from the start of the first ``bench.call`` span to the
end of the last one.  Times are kept in picoseconds, as the trace has
them.
"""
from __future__ import annotations

import re
from collections import Counter
from dataclasses import dataclass, field

import numpy as np

import xplane

CONTAINERS = frozenset({"while", "conditional", "call"})
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"


def union(starts, ends) -> np.ndarray:
    """Disjoint, sorted ``[k, 2]`` intervals covering the given ones."""
    s = np.asarray(starts, np.int64)
    e = np.asarray(ends, np.int64)
    if s.size == 0:
        return np.zeros((0, 2), np.int64)
    order = np.argsort(s, kind="stable")
    s, e = s[order], e[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > reach[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, s.size - 1)
    return np.stack([s[first], reach[last]], axis=1)


def clip(iv: np.ndarray, lo: int, hi: int) -> np.ndarray:
    out = np.stack([np.maximum(iv[:, 0], lo), np.minimum(iv[:, 1], hi)], 1)
    return out[out[:, 1] > out[:, 0]]


def length(iv: np.ndarray) -> int:
    return int((iv[:, 1] - iv[:, 0]).sum()) if len(iv) else 0


def intersect(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Intersection of two disjoint sorted interval sets."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i, 0], b[j, 0]), min(a[i, 1], b[j, 1])
        if hi > lo:
            out.append((lo, hi))
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return np.asarray(out, np.int64).reshape(-1, 2)


def complement(iv: np.ndarray, lo: int, hi: int) -> np.ndarray:
    iv = clip(iv, lo, hi)
    edges = np.concatenate([[lo], iv.reshape(-1), [hi]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


@dataclass
class Device:
    name: str
    busy: np.ndarray  # union of operation intervals, clipped to the window
    scopes: dict[str, np.ndarray]
    collectives: np.ndarray  # collectives, asynchronous ones included
    modules: dict[str, np.ndarray]  # module name -> its spans
    ops: Counter = field(default_factory=Counter)  # label -> ps

    @property
    def busy_ps(self) -> int:
        return length(self.busy)

    def scope_ps(self, scope: str) -> int:
        return length(self.scopes.get(scope, np.zeros((0, 2), np.int64)))

    def busy_in(self, module: str) -> int:
        spans = self.modules.get(module)
        return 0 if spans is None else length(intersect(self.busy, spans))

    def module_of(self, scope: str) -> str:
        """The module whose spans hold most of ``scope``'s operations."""
        ops = self.scopes.get(scope, np.zeros((0, 2), np.int64))
        held = {m: length(intersect(ops, spans))
                for m, spans in self.modules.items()}
        if not held or max(held.values()) == 0:
            raise ValueError(f"{self.name}: no XLA module holds operations "
                             f"under the scope {scope!r}")
        return max(held, key=held.get)


@dataclass
class Reduced:
    window: tuple[int, int]
    devices: list[Device]
    calls: np.ndarray  # host spans of the harness's calls
    program: np.ndarray  # host spans of the program's runs
    gaps: list[tuple[str, int]]  # idle gaps on the fullest device

    @property
    def window_ps(self) -> int:
        return self.window[1] - self.window[0]

    def fullest(self) -> Device:
        return max(self.devices, key=lambda d: d.busy_ps)


def _label(name: str, category: str, scopes: tuple) -> str:
    """An operation's name without its number, with its innermost scope
    (the first of ``scopes`` it is in) or else its category."""
    op = re.sub(r"[.\d]+$", "", name.split(" ")[0].lstrip("%"))
    return f"{op} [{scopes[0] if scopes else category}]"


def _host_spans(space, names):
    spans = {n: ([], []) for n in names}
    for plane in space.planes:
        if plane.name != HOST_PLANE:
            continue
        meta = {k: v.name for k, v in plane.event_metadata.items()}
        for line in plane.lines:
            base = line.timestamp_ns * 1000
            for ev in line.events:
                name = meta.get(ev.metadata_id)
                if name in spans:
                    spans[name][0].append(base + ev.offset_ps)
                    spans[name][1].append(base + ev.offset_ps + ev.duration_ps)
    return {n: union(*se) for n, se in spans.items()}


def reduce(path: str, *, scopes=("bench.logp", "pcvm.block"),
           call_span: str = "bench.call",
           program_span: str = "pcvm.run") -> Reduced:
    space = xplane.load(path)
    host = _host_spans(space, (call_span, program_span))
    calls, program = host[call_span], host[program_span]
    raw = []
    for plane in space.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        info = {}
        for k, em in plane.event_metadata.items():
            st = xplane.stats(em, names)
            tf_op = str(st.get("tf_op", ""))
            category = str(st.get("hlo_category", ""))
            info[k] = (em.name, category,
                       tuple(s for s in scopes if s in tf_op),
                       bool(COLLECTIVE.search(category) or
                            COLLECTIVE.search(em.name.split("=")[0])))
        ops, mods = [], {}
        for line in plane.lines:
            base = line.timestamp_ns * 1000
            if line.name == "Async XLA Ops":
                # An asynchronous collective runs beside the operations;
                # it counts as a collective, not as busy time.
                for ev in line.events:
                    name, category, scope, coll = info[ev.metadata_id]
                    if coll:
                        start = base + ev.offset_ps
                        ops.append((start, start + ev.duration_ps,
                                    name, category, scope, coll, True))
            elif line.name == "XLA Ops":
                for ev in line.events:
                    name, category, scope, coll = info[ev.metadata_id]
                    if category in CONTAINERS:
                        continue
                    start = base + ev.offset_ps
                    ops.append((start, start + ev.duration_ps,
                                name, category, scope, coll, False))
            elif line.name == "XLA Modules":
                for ev in line.events:
                    # "jit__loop(1234)": the program's name and its id.
                    name = info[ev.metadata_id][0].split("(")[0]
                    start = base + ev.offset_ps
                    span = mods.setdefault(name, ([], []))
                    span[0].append(start)
                    span[1].append(start + ev.duration_ps)
        raw.append((plane.name, ops, {m: union(*se) for m, se in mods.items()}))
    if not raw or not any(not o[6] for _, ops, _ in raw for o in ops):
        raise ValueError(f"{path}: no device operations in the trace")
    if len(calls):
        window = (int(calls[0, 0]), int(calls[-1, 1]))
    else:
        window = (min(o[0] for _, ops, _ in raw for o in ops),
                  max(o[1] for _, ops, _ in raw for o in ops))
    devices = []
    for name, ops, mods in raw:
        s = np.array([o[0] for o in ops], np.int64)
        e = np.array([o[1] for o in ops], np.int64)
        sync = [i for i, o in enumerate(ops) if not o[6]]
        busy = clip(union(s[sync], e[sync]), *window)
        per_scope = {}
        for sc in scopes:
            idx = [i for i in sync if sc in ops[i][4]]
            per_scope[sc] = clip(union(s[idx], e[idx]), *window)
        idx = [i for i, o in enumerate(ops) if o[5]]
        coll = clip(union(s[idx], e[idx]), *window)
        tally = Counter()
        lo, hi = window
        for o in ops:
            if o[6]:
                continue
            d = min(o[1], hi) - max(o[0], lo)
            if d > 0:
                tally[_label(o[2], o[3], o[4])] += d
        devices.append(Device(name, busy, per_scope, coll, mods, tally))
    fullest = max(devices, key=lambda d: d.busy_ps)
    gaps = []
    for lo, hi in complement(fullest.busy, *window):
        mid = (lo + hi) // 2
        if _inside(program, mid):
            label = program_span
        elif _inside(calls, mid):
            label = call_span
        else:
            label = "harness"
        gaps.append((label, int(hi - lo)))
    return Reduced(window, devices, calls, program, gaps)


def _inside(iv: np.ndarray, t: int) -> bool:
    if not len(iv):
        return False
    i = np.searchsorted(iv[:, 0], t, side="right") - 1
    return bool(i >= 0 and iv[i, 1] > t)
