"""The trace reduction: busy union, time per scope and gap attribution,
on a trace built by hand and on a small trace recorded on a TPU v5e."""
import re
from pathlib import Path

import numpy as np
import pytest

import tracereduce
import xplane

RECORDED = Path(__file__).parent / "data" / "nuts_tiny_v5e.xplane.pb"


def _space(tmp_path):
    """A device with a ``while`` holding four operations, and a host with
    one harness call holding one program run.  Times in ns."""
    sp = xplane.XSpace()
    dev = sp.planes.add(name="/device:TPU:0")
    stat_names = {1: "hlo_category", 2: "tf_op"}
    for k, v in stat_names.items():
        dev.stat_metadata[k].id = k
        dev.stat_metadata[k].name = v
    ops = {  # id: (name, category, tf_op)
        1: ("%while.1 = while()", "while", "jit(_loop)/while"),
        2: ("%fusion.1 = fusion()", "loop fusion",
            "jit(_loop)/while/body/pcvm.block3/vmap(bench.logp)/dot"),
        3: ("%fusion.2 = fusion()", "loop fusion",
            "jit(_loop)/while/body/pcvm.block3/transpose(jvp(bench.logp))/dot"),
        4: ("%all-reduce.7 = all-reduce()", "all-reduce",
            "jit(_loop)/while/body/reduce_or"),
        5: ("%copy.3 = copy()", "data formatting", "jit(_loop)/while/body"),
        6: ("jit__loop(123)", "", ""),
    }
    for k, (name, cat, tf) in ops.items():
        em = dev.event_metadata[k]
        em.id, em.name = k, name
        for sid, val in ((1, cat), (2, tf)):
            st = em.stats.add(metadata_id=sid)
            st.str_value = val
    line = dev.lines.add(name="XLA Ops", timestamp_ns=0)
    for mid, start, dur in [(1, 100, 900), (2, 120, 30), (3, 140, 40),
                            (4, 300, 50), (5, 500, 100)]:
        line.events.add(metadata_id=mid, offset_ps=start * 1000,
                        duration_ps=dur * 1000)
    mods = dev.lines.add(name="XLA Modules", timestamp_ns=0)
    mods.events.add(metadata_id=6, offset_ps=100_000, duration_ps=900_000)
    host = sp.planes.add(name="/host:CPU")
    for k, name in ((1, "bench.call"), (2, "pcvm.run")):
        host.event_metadata[k].id = k
        host.event_metadata[k].name = name
    py = host.lines.add(name="python", timestamp_ns=50)
    py.events.add(metadata_id=1, offset_ps=0, duration_ps=1_000_000)
    py.events.add(metadata_id=2, offset_ps=30_000, duration_ps=600_000)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(sp.SerializeToString())
    return path


def test_hand_built_trace(tmp_path):
    r = tracereduce.reduce(str(_space(tmp_path)))
    assert r.window == (50_000, 1_050_000)
    d = r.fullest()
    # Leaves only: [120, 180) (two overlapping), [300, 350), [500, 600).
    assert d.busy_ps == (60 + 50 + 100) * 1000
    assert d.scope_ps("bench.logp") == 60 * 1000
    assert d.scope_ps("pcvm.block") == 60 * 1000
    assert tracereduce.length(d.collectives) == 50 * 1000
    # The VM's loop is found by the scope of its blocks, not by its name.
    assert d.module_of("pcvm.block") == "jit__loop"
    assert d.busy_in("jit__loop") == 210 * 1000
    with pytest.raises(ValueError):
        d.module_of("no.such.scope")
    # Idle: [50,120), [180,300) and [350,500) have their middles inside
    # pcvm.run (80..680); [600,1050) has its middle (825) in bench.call
    # only.
    assert r.gaps == [("pcvm.run", 70_000), ("pcvm.run", 120_000),
                      ("pcvm.run", 150_000), ("bench.call", 450_000)]
    assert sum(g for _, g in r.gaps) == r.window_ps - d.busy_ps
    assert d.ops["fusion [bench.logp]"] == 70 * 1000


def test_union_and_complement():
    iv = tracereduce.union([5, 0, 20, 8], [10, 6, 30, 9])
    assert iv.tolist() == [[0, 10], [20, 30]]
    assert tracereduce.complement(iv, 0, 40).tolist() == [[10, 20], [30, 40]]
    assert tracereduce.intersect(iv, np.array([[8, 25]])).tolist() == [
        [8, 10], [20, 25]]


@pytest.fixture(scope="module")
def recorded():
    return tracereduce.reduce(str(RECORDED))


def _leaf_union_by_profiledata(path):
    """The busy union again, through JAX's own trace reader: a second
    witness that reads the same file by another path.  Control-flow
    operations are known here by their HLO text, not their category."""
    import jax

    pd = jax.profiler.ProfileData.from_file(str(path))
    plane = pd.find_plane_with_name("/device:TPU:0")
    line = next(ln for ln in plane.lines if ln.name == "XLA Ops")
    ctrl = re.compile(r"\b(while|conditional|call)\(")
    s, e = [], []
    for ev in line.events:
        if ctrl.search(ev.name):
            continue
        s.append(round(ev.start_ns * 1000))
        e.append(round(ev.end_ns * 1000))
    return tracereduce.union(s, e)


def test_recorded_trace_busy_union(recorded):
    d = recorded.fullest()
    want = tracereduce.clip(_leaf_union_by_profiledata(RECORDED),
                            *recorded.window)
    # ProfileData rounds to whole nanoseconds.
    assert abs(d.busy_ps - tracereduce.length(want)) <= 2000 * len(want)
    assert 0 < d.busy_ps < recorded.window_ps


def test_recorded_trace_scopes_and_gaps(recorded):
    d = recorded.fullest()
    # The density's scope reaches the device ops, the transposed
    # gradient's among them; every such op lies inside a VM block scope.
    assert 0 < d.scope_ps("bench.logp") < d.scope_ps("pcvm.block")
    loop = d.module_of("pcvm.block")
    assert loop == "jit__loop"
    assert 0 < d.busy_in(loop) <= d.busy_ps
    assert any("bench.logp" in k for k in d.ops)
    assert len(recorded.calls) >= 2
    labels = {label for label, _ in recorded.gaps}
    assert labels <= {"pcvm.run", "bench.call", "harness"}
    assert sum(g for _, g in recorded.gaps) == (
        recorded.window_ps - d.busy_ps)
    # Between two calls the device waits on host code.
    assert "pcvm.run" in labels
