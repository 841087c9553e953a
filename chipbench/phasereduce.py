"""Split a traced window by the program's own names.

``tracereduce`` reads the trace against two host spans (``bench.call``,
``pcvm.run``) and the ``pcvm.block`` scope.  This reduction reads the
rest of what the program names, on the same device timeline:

* host spans: ``bench.call`` and every span whose name starts with
  ``autobatch.`` or ``pcvm.``.  The program's phases nest
  (``autobatch.call`` > ``autobatch.bind`` | ``pcvm.run`` |
  ``autobatch.check``; ``pcvm.run`` > ``pcvm.start`` | ``pcvm.launch`` |
  ``pcvm.result``; the call's first blocking read is ``pcvm.wait``), so
  every instant has one innermost span;
* each device operation's innermost program scope: the last ``pcvm.``
  name in its op name (``pcvm.block<i>`` read as ``pcvm.block``), or
  ``unscoped`` where it carries none, as the copies XLA inserts do.

From them, on the fullest device, inside the window of the harness's
calls:

* ``loop_busy``: the VM loop program's busy time, partitioned by the
  innermost scope of the operation running at each instant (where two
  overlap, the one that started first keeps the time), so that the parts
  add up to the loop's busy time;
* ``loop_gaps``: the idle time inside the loop program's spans, each gap
  labelled ``"<before> > <after>"`` by the scopes of the operations on its
  two sides;
* ``host_gaps``: the idle time outside the loop program, each gap put
  under the innermost host span at its middle (``harness`` between calls);
* ``clock_margins``: per call, how long before the end of ``pcvm.wait``
  the loop program's last operation ended.  The host cannot return from
  that read before the loop has ended, so a negative margin means the host
  and device clocks disagree, and ``check_clock`` refuses the trace.

The loop program is the module that holds the ``pcvm.block`` operations,
as ``tracereduce.Device.module_of`` finds it.  Times are picoseconds.
"""
from __future__ import annotations

import re
from collections import Counter
from dataclasses import dataclass

import numpy as np

import tracereduce as tr
import xplane

PREFIXES = ("autobatch.", "pcvm.")
CALL_SPAN = "bench.call"
LOOP_SCOPE = "pcvm.block"
UNSCOPED = "unscoped"
_BLOCK = re.compile(r"^pcvm\.block\d+$")

#: Scopes of each per-dispatch share of the loop's busy time.
STATE = ("pcvm.write", "pcvm.push", "pcvm.pop")
CONTROL = ("pcvm.pick", "pcvm.stats", "pcvm.cond", "pcvm.compact",
           "pcvm.switch")
#: Host spans of each per-call share of the idle time outside the loop.
LAUNCH = ("autobatch.bind", "pcvm.start", "pcvm.launch")
SYNC = ("pcvm.wait", "pcvm.result", "autobatch.check")


def innermost_scope(tf_op: str) -> str:
    """The last ``pcvm.`` name in an op name, ``pcvm.block<i>`` read as
    ``pcvm.block``; ``unscoped`` where there is none."""
    for part in reversed(tf_op.split("/")):
        if part.startswith("pcvm."):
            return LOOP_SCOPE if _BLOCK.match(part) else part
    return UNSCOPED


@dataclass
class Phases:
    loop: str  # the loop program's module name
    loop_busy: Counter  # innermost scope -> busy ps inside the loop
    loop_gaps: Counter  # "<before> > <after>" -> idle ps inside the loop
    host_gaps: Counter  # innermost host span -> idle ps outside the loop
    host_spans: Counter  # host span name -> spans of it in the window
    clock_margins: list  # ps, one per call with a pcvm.wait span
    unscoped_ops: Counter  # operation name -> busy ps, unscoped in the loop
    calls: int  # harness calls in the window

    @property
    def loop_idle_ps(self) -> int:
        return sum(self.loop_gaps.values())

    def named(self) -> bool:
        """Whether the program named its parts (scopes beside the blocks'
        and host phases beside ``pcvm.run``)."""
        return any(s in self.loop_busy for s in STATE + CONTROL) and any(
            s in self.host_spans for s in LAUNCH + SYNC)


def _host_spans(space, call_span):
    """``(start, end, name)`` of every host span the reduction reads, in
    order of start, an outer span before one it holds that starts with
    it."""
    out = []
    for plane in space.planes:
        if plane.name != tr.HOST_PLANE:
            continue
        meta = {k: v.name for k, v in plane.event_metadata.items()}
        for line in plane.lines:
            base = line.timestamp_ns * 1000
            for ev in line.events:
                name = meta.get(ev.metadata_id, "")
                if name == call_span or name.startswith(PREFIXES):
                    s = base + ev.offset_ps
                    out.append((s, s + ev.duration_ps, name))
    return sorted(out, key=lambda x: (x[0], -x[1]))


def _device_ops(plane):
    """The operations of one device plane as arrays (start, end, name
    code, innermost-scope code, whether a ``pcvm.block`` scope holds it)
    with the names and scopes the codes index, and each module's spans.
    Control-flow containers and asynchronous collectives are left out,
    as ``tracereduce`` leaves them out of busy time."""
    names = {k: v.name for k, v in plane.stat_metadata.items()}
    info, op_names, scopes = {}, {}, {}
    for k, em in plane.event_metadata.items():
        st = xplane.stats(em, names)
        tf_op = str(st.get("tf_op", ""))
        info[k] = (em.name, str(st.get("hlo_category", "")),
                   op_names.setdefault(em.name, len(op_names)),
                   scopes.setdefault(innermost_scope(tf_op), len(scopes)),
                   LOOP_SCOPE in tf_op)
    cols, mods = ([], [], [], [], []), {}
    for line in plane.lines:
        base = line.timestamp_ns * 1000
        if line.name == "XLA Ops":
            for ev in line.events:
                name, category, n, sc, blk = info[ev.metadata_id]
                if category in tr.CONTAINERS:
                    continue
                s = base + ev.offset_ps
                for col, v in zip(cols, (s, s + ev.duration_ps, n, sc, blk)):
                    col.append(v)
        elif line.name == "XLA Modules":
            for ev in line.events:
                name = info[ev.metadata_id][0].split("(")[0]
                s = base + ev.offset_ps
                span = mods.setdefault(name, ([], []))
                span[0].append(s)
                span[1].append(s + ev.duration_ps)
    s, e, n, sc = (np.asarray(c, np.int64) for c in cols[:4])
    order = np.argsort(s, kind="stable")
    ops = (s[order], e[order], n[order], sc[order],
           np.asarray(cols[4], bool)[order])
    return (ops, list(op_names), list(scopes),
            {m: tr.union(*se) for m, se in mods.items()})


def _innermost(spans, t):
    """The innermost host span holding ``t`` (the latest to start)."""
    best = None
    for s, e, name in spans:
        if s > t:
            break
        if e > t:
            best = name
    return best


def _inside(spans: np.ndarray, t: np.ndarray) -> np.ndarray:
    i = np.searchsorted(spans[:, 0], t, side="right") - 1
    return (i >= 0) & (spans[np.maximum(i, 0), 1] > t)


def reduce(path: str, *, call_span: str = CALL_SPAN) -> Phases:
    space = xplane.load(path)
    host = _host_spans(space, call_span)
    calls = tr.union([s for s, _, n in host if n == call_span],
                     [e for _, e, n in host if n == call_span])
    if not len(calls):
        raise ValueError(f"{path}: no {call_span!r} span in the trace")
    lo, hi = int(calls[0, 0]), int(calls[-1, 1])
    best = None
    for plane in space.planes:
        if not tr.DEVICE_PLANE.match(plane.name):
            continue
        dev = _device_ops(plane)
        busy = tr.clip(tr.union(dev[0][0], dev[0][1]), lo, hi)
        if best is None or tr.length(busy) > tr.length(best[1]):
            best = (plane.name, busy, dev)
    if best is None:
        raise ValueError(f"{path}: no device plane in the trace")
    device, busy, ((s, e, n, sc, blk), op_names, scopes, mods) = best
    scoped = tr.union(s[blk], e[blk])
    held = {m: tr.length(tr.intersect(scoped, sp)) for m, sp in mods.items()}
    if not held or max(held.values()) == 0:
        raise ValueError(f"{device}: no XLA module holds operations under "
                         f"the scope {LOOP_SCOPE!r}")
    loop = max(held, key=held.get)
    spans = tr.clip(mods[loop], lo, hi)

    # The loop's operations, clipped to the window, in order of start.
    keep = _inside(spans, s)
    s, e = np.clip(s[keep], lo, hi), np.clip(e[keep], lo, hi)
    n, sc = n[keep], sc[keep]
    keep = e > s
    s, e, n, sc = s[keep], e[keep], n[keep], sc[keep]
    # Each operation keeps the part of it that no earlier one covers.
    reach = np.maximum.accumulate(np.concatenate([[lo], e]))[:-1]
    own = np.maximum(e - np.maximum(s, reach), 0)
    loop_busy = Counter({scopes[k]: int(v) for k, v in enumerate(
        np.bincount(sc, weights=own, minlength=len(scopes))) if v})
    un = sc == scopes.index(UNSCOPED) if UNSCOPED in scopes else sc < 0
    unscoped = Counter({op_names[k]: int(v) for k, v in enumerate(
        np.bincount(n[un], weights=own[un], minlength=len(op_names)))
        if v})

    # Gaps inside the loop's spans, between the operation that reached
    # furthest before each and the one that starts it off again.
    furthest = np.maximum.accumulate(
        np.where(e >= np.maximum.accumulate(e), np.arange(e.size), 0))
    loop_gaps = Counter()
    ran = tr.union(s, e)
    for a, b in spans:
        gaps = tr.complement(ran, int(a), int(b))
        if not len(gaps):
            continue
        i = np.searchsorted(s, gaps[:, 0], side="right") - 1
        j = np.searchsorted(s, gaps[:, 1], side="left")
        for (g0, g1), ii, jj in zip(gaps, i, j):
            before = (scopes[sc[furthest[ii]]] if ii >= 0 and s[ii] >= a
                      else "start")
            after = scopes[sc[jj]] if jj < s.size and s[jj] < b else "end"
            loop_gaps[f"{before} > {after}"] += int(g1 - g0)

    host_gaps = Counter()
    outside = tr.union(np.concatenate([busy[:, 0], spans[:, 0]]),
                       np.concatenate([busy[:, 1], spans[:, 1]]))
    for g0, g1 in tr.complement(outside, lo, hi):
        label = _innermost(host, (g0 + g1) // 2) or "harness"
        host_gaps[label] += int(g1 - g0)

    margins = []
    for h0, h1, name in host:
        if name != "pcvm.wait" or not (lo <= h0 < hi):
            continue
        k = np.searchsorted(spans[:, 0], h1, side="left") - 1
        if k < 0:
            continue
        mine = e[(s >= spans[k, 0]) & (s < spans[k, 1])]
        if mine.size:
            margins.append(int(h1 - mine.max()))
    counted = Counter(name for h0, _, name in host if lo <= h0 < hi)
    return Phases(loop, loop_busy, loop_gaps, host_gaps, counted, margins,
                  unscoped, len(calls))


def check_clock(ph: Phases) -> int:
    """The smallest clock margin in ps; raises where one is negative."""
    if not ph.clock_margins:
        raise ValueError("no pcvm.wait span follows a loop in the trace")
    low = min(ph.clock_margins)
    if low < 0:
        raise ValueError(f"the loop's last operation ends {-low} ps after "
                         "the host's pcvm.wait returned: host and device "
                         "clocks disagree")
    return low


def metrics(ph: Phases, steps: int) -> dict:
    """The per-layer readings of this reduction, ``steps`` being the VM
    dispatches of the traced calls.  A reading the program does not name
    (a program without the scopes or phases) is left out."""
    if not steps:
        return {}
    out = {"loop_idle_us.nuts": ph.loop_idle_ps / 1e6 / steps}
    if not ph.named():
        return out

    def per_dispatch(scopes):
        return sum(ph.loop_busy[s] for s in scopes) / 1e6 / steps

    def per_call(spans):
        return sum(ph.host_gaps[s] for s in spans) / 1e9 / ph.calls

    out.update({
        "state_us_per_dispatch.nuts": per_dispatch(STATE),
        "control_us_per_dispatch.nuts": per_dispatch(CONTROL),
        "unscoped_us_per_dispatch.nuts": per_dispatch((UNSCOPED,)),
        "launch_gap_ms.nuts": per_call(LAUNCH),
        "sync_gap_ms.nuts": per_call(SYNC),
    })
    return out


def breakdown(ph: Phases) -> dict:
    """Seconds by label: the loop's gaps (the ten largest) and the idle
    time outside the loop under each host phase."""
    return {
        "loop_gaps": [[k, v / 1e12] for k, v in ph.loop_gaps.most_common(10)],
        "host_phases": [[k, v / 1e12] for k, v in ph.host_gaps.most_common()],
    }
