"""The split of a traced window by the program's own names
(``phasereduce``) and the ``loop_idle_us.nuts`` reader, on a trace built
by hand and on small traces recorded on a TPU v5e."""
import gzip
import shutil
from pathlib import Path
from types import SimpleNamespace

import pytest

import cell
import phasereduce
import tracereduce
import xplane

DATA = Path(__file__).parent / "data"
OLD = DATA / "nuts_tiny_v5e.xplane.pb"  # recorded before the phases
LOOP = "jit(_loop)/while/body"
BLOCK1 = f"{LOOP}/pcvm.switch/cond/branch_1_fun/pcvm.block1"
BLOCK2 = f"{LOOP}/pcvm.switch/cond/branch_2_fun/pcvm.block2"

# Device operations: (module, name, category, tf_op, start, end), in ns.
OPS = [
    ("jit__start", "%fusion.1", "loop fusion", "jit(_start)/broadcast",
     65, 80),
    ("jit__start", "%fusion.2", "loop fusion", "jit(_start)/iota", 100, 105),
    ("jit__loop", "%while.1", "while", "jit(_loop)/while", 120, 780),
    ("jit__loop", "%fusion.3", "loop fusion", f"{LOOP}/pcvm.pick/reduce_min",
     125, 135),
    ("jit__loop", "%fusion.4", "loop fusion", f"{LOOP}/pcvm.stats/add",
     135, 140),
    ("jit__loop", "%clamp.1", "non-fusion elementwise",
     f"{LOOP}/pcvm.switch/clamp", 140, 142),
    ("jit__loop", "%select.1", "loop fusion",
     f"{BLOCK1}/pcvm.write/select_n", 150, 170),
    ("jit__loop", "%fusion.5", "convolution fusion",
     f"{BLOCK1}/pcvm.prim.grad/vmap(bench.logp)/dot", 170, 250),
    ("jit__loop", "%copy.1", "data formatting", "", 260, 400),
    ("jit__loop", "%scatter.1", "scatter",
     f"{BLOCK2}/pcvm.push/scatter", 400, 450),
    ("jit__loop", "%gather.1", "gather", f"{BLOCK2}/pcvm.pop/gather",
     440, 500),
    ("jit__loop", "%select.2", "loop fusion", f"{BLOCK2}/select_n", 500, 520),
    ("jit__loop", "%fusion.6", "loop fusion",
     "jit(_loop)/while/cond/pcvm.cond/reduce_or", 600, 610),
    ("jit__loop", "%sort.1", "sort", f"{LOOP}/pcvm.compact/sort", 610, 620),
    ("jit__loop", "%copy.2", "data formatting", "", 700, 760),
    ("jit_take", "%gather.2", "gather", "jit(take)/gather", 820, 840),
    ("jit_any", "%fusion.7", "loop fusion", "jit(any)/reduce_or", 910, 915),
    ("jit_fold_in", "%fusion.8", "loop fusion", "jit(fold_in)/add",
     960, 970),
]
# Host spans: (name, start, end), in ns; "$python" is not the program's.
SPANS = [
    ("bench.call", 0, 1000), ("autobatch.call", 10, 975),
    ("autobatch.bind", 10, 60), ("pcvm.run", 60, 900),
    ("pcvm.start", 60, 110), ("pcvm.launch", 110, 150),
    ("pcvm.result", 150, 900), ("pcvm.wait", 160, 810),
    ("$python.py:1 helper", 200, 300), ("autobatch.check", 900, 950),
]
STEPS = 5


def _space(tmp_path, spans=SPANS):
    sp = xplane.XSpace()
    dev = sp.planes.add(name="/device:TPU:0")
    for k, v in {1: "hlo_category", 2: "tf_op"}.items():
        dev.stat_metadata[k].id = k
        dev.stat_metadata[k].name = v
    ops = dev.lines.add(name="XLA Ops", timestamp_ns=0)
    mods = dev.lines.add(name="XLA Modules", timestamp_ns=0)
    modules = {}
    for k, (mod, name, cat, tf, start, end) in enumerate(OPS, 1):
        em = dev.event_metadata[k]
        em.id, em.name = k, name
        for sid, val in ((1, cat), (2, tf)):
            em.stats.add(metadata_id=sid).str_value = val
        ops.events.add(metadata_id=k, offset_ps=start * 1000,
                       duration_ps=(end - start) * 1000)
        lo, hi = modules.get(mod, (start, end))
        modules[mod] = (min(lo, start), max(hi, end))
    for k, (mod, (start, end)) in enumerate(modules.items(), 100):
        dev.event_metadata[k].id = k
        dev.event_metadata[k].name = f"{mod}({k})"
        mods.events.add(metadata_id=k, offset_ps=start * 1000,
                        duration_ps=(end - start) * 1000)
    host = sp.planes.add(name="/host:CPU")
    py = host.lines.add(name="python3", timestamp_ns=0)
    for k, (name, start, end) in enumerate(spans, 1):
        host.event_metadata[k].id = k
        host.event_metadata[k].name = name
        py.events.add(metadata_id=k, offset_ps=start * 1000,
                      duration_ps=(end - start) * 1000)
    path = tmp_path / "phases.xplane.pb"
    path.write_bytes(sp.SerializeToString())
    return path


@pytest.fixture()
def hand(tmp_path):
    path = _space(tmp_path)
    return path, phasereduce.reduce(str(path))


def test_loop_busy_partition(hand):
    _, ph = hand
    assert ph.loop == "jit__loop" and ph.calls == 1
    ns = {k: v // 1000 for k, v in ph.loop_busy.items()}
    # The push started first, so it keeps [440, 450) where the pop
    # overlaps it.
    assert ns == {"pcvm.pick": 10, "pcvm.stats": 5, "pcvm.switch": 2,
                  "pcvm.write": 20, "pcvm.prim.grad": 80, "unscoped": 200,
                  "pcvm.push": 50, "pcvm.pop": 50, "pcvm.block": 20,
                  "pcvm.cond": 10, "pcvm.compact": 10}
    assert {k: v // 1000 for k, v in ph.unscoped_ops.items()} == {
        "%copy.1": 140, "%copy.2": 60}


def test_loop_gaps_by_their_neighbours(hand):
    _, ph = hand
    assert {k: v // 1000 for k, v in ph.loop_gaps.items()} == {
        "start > pcvm.pick": 5, "pcvm.switch > pcvm.write": 8,
        "pcvm.prim.grad > unscoped": 10, "pcvm.block > pcvm.cond": 80,
        "pcvm.compact > unscoped": 80, "unscoped > end": 20}
    assert ph.loop_idle_ps == 203_000


def test_host_gaps_by_innermost_span(hand):
    _, ph = hand
    assert {k: v // 1000 for k, v in ph.host_gaps.items()} == {
        "autobatch.bind": 65, "pcvm.start": 20, "pcvm.launch": 15,
        "pcvm.wait": 40, "pcvm.result": 70, "autobatch.check": 45,
        "bench.call": 30}
    assert "$python.py:1 helper" not in ph.host_spans
    assert ph.named()


def test_clock_check(hand, tmp_path):
    _, ph = hand
    # pcvm.wait ends at 810 ns, the loop's last operation at 760 ns.
    assert ph.clock_margins == [50_000]
    assert phasereduce.check_clock(ph) == 50_000
    early = [(n, s, 700 if n == "pcvm.wait" else e) for n, s, e in SPANS]
    sub = tmp_path / "early"
    sub.mkdir()
    bad = phasereduce.reduce(str(_space(sub, early)))
    assert bad.clock_margins == [-60_000]
    with pytest.raises(ValueError, match="clocks disagree"):
        phasereduce.check_clock(bad)


def _ctx(reduced):
    return SimpleNamespace(trace=reduced, traced=[{"steps": STEPS}],
                           calls=[{"steps": STEPS}], traj=1)


def _benchmark_readings(path) -> dict:
    """The accepted readers' values on a trace, by name."""
    red = tracereduce.reduce(str(path), call_span=cell.CALL_SPAN)
    out = {}
    for m, reader in cell.readers(cell.benchmark(), "corrgauss-1024"):
        if m["name"] in ("idle_share.nuts", "host_gap_ms.nuts",
                         "us_per_dispatch.nuts", "loop_idle_us.nuts"):
            out[m["name"]] = reader.read(_ctx(red))
    return out, red


@pytest.mark.parametrize("name, want", [
    ("loop_idle_us.nuts", 203e3 / 1e6 / STEPS),
    ("state_us_per_dispatch.nuts", 120e3 / 1e6 / STEPS),
    ("control_us_per_dispatch.nuts", 37e3 / 1e6 / STEPS),
    ("unscoped_us_per_dispatch.nuts", 200e3 / 1e6 / STEPS),
    ("launch_gap_ms.nuts", 100e3 / 1e9),
    ("sync_gap_ms.nuts", 155e3 / 1e9),
])
def test_metric_readings(hand, name, want):
    _, ph = hand
    assert phasereduce.metrics(ph, STEPS)[name] == pytest.approx(want)


def test_accounting_closes(hand):
    path, ph = hand
    old, red = _benchmark_readings(path)
    new = phasereduce.metrics(ph, STEPS)
    window = red.window_ps
    idle = window * old["idle_share.nuts"] / 100
    # In-loop idle and idle outside the loop make up the window's idle.
    assert new["loop_idle_us.nuts"] == pytest.approx(old["loop_idle_us.nuts"])
    assert (old["loop_idle_us.nuts"] * 1e6 * STEPS
            + old["host_gap_ms.nuts"] * 1e9) == pytest.approx(idle)
    # Launch and sync, with the harness's own gap, make up host_gap_ms.
    harness = ph.host_gaps["bench.call"] / 1e9
    assert (new["launch_gap_ms.nuts"] + new["sync_gap_ms.nuts"] + harness
            == pytest.approx(old["host_gap_ms.nuts"]))
    # The partition of the loop's busy time makes up us_per_dispatch.
    assert (sum(ph.loop_busy.values()) / 1e6 / STEPS
            == pytest.approx(old["us_per_dispatch.nuts"]))
    assert phasereduce.breakdown(ph)["host_phases"][0] == [
        "pcvm.result", 70e-9]


def test_an_unnamed_program_reads_loop_idle_only():
    """A trace of a program without the phases and the new scopes: the
    loop's idle time still reads, the split reads nothing, and nothing
    raises."""
    ph = phasereduce.reduce(str(OLD))
    assert not ph.named()
    assert set(ph.loop_busy) <= {"pcvm.block", "unscoped"}
    old, _ = _benchmark_readings(OLD)
    got = phasereduce.metrics(ph, STEPS)
    assert set(got) == {"loop_idle_us.nuts"}
    assert got["loop_idle_us.nuts"] == pytest.approx(
        old["loop_idle_us.nuts"])
    assert old["loop_idle_us.nuts"] > 0


# Two calls of corrgauss-1024 cut to 16 chains and tree depth 1, traced on
# a TPU v5e with the program's phases and scopes (``phases.py --record``),
# gzipped.
RECORDED = DATA / "nuts_phases_v5e.xplane.pb.gz"


@pytest.fixture(scope="module")
def recorded_path(tmp_path_factory):
    path = tmp_path_factory.mktemp("recorded") / "phases.xplane.pb"
    with gzip.open(RECORDED, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return path


@pytest.fixture(scope="module")
def recorded(recorded_path):
    return phasereduce.reduce(str(recorded_path))


def test_recorded_clock_check(recorded):
    """On the chip the loop's last operation ended before the host came
    back from ``pcvm.wait``, in each of the two calls."""
    assert recorded.calls == 2 and len(recorded.clock_margins) == 2
    assert phasereduce.check_clock(recorded) > 0


def test_recorded_trace_is_named_and_accounts(recorded, recorded_path):
    ph = recorded
    assert ph.named()
    for span in ("autobatch.call", "autobatch.bind", "pcvm.start",
                 "pcvm.launch", "pcvm.wait", "pcvm.result",
                 "autobatch.check"):
        assert ph.host_spans[span] == 2, span
    # Every scope the loop runs reaches the chip (pcvm.switch holds only
    # the block bodies there, and nothing compacts).
    for scope in ("pcvm.pick", "pcvm.stats", "pcvm.cond", "pcvm.write",
                  "pcvm.push", "pcvm.pop", "pcvm.prim.grad", "pcvm.block"):
        assert ph.loop_busy[scope] > 0, scope
    old, red = _benchmark_readings(recorded_path)
    d = red.fullest()
    assert sum(ph.loop_busy.values()) == d.busy_in(ph.loop)
    steps = 100  # any count: both sides divide by it
    new = phasereduce.metrics(ph, steps)
    ctx = SimpleNamespace(trace=red, traced=[{"steps": steps}])
    reader = cell.load_module(cell.HERE / "metrics" / "loop_idle_us.nuts.py")
    assert new["loop_idle_us.nuts"] == pytest.approx(reader.read(ctx))
    idle = red.window_ps - d.busy_ps
    assert ph.loop_idle_ps + sum(ph.host_gaps.values()) == idle
