"""Every name in BENCHMARK.json leads to its files, and the files agree
with it."""
import re

import pytest

import cell

BENCH = cell.benchmark()
E2E = {m["name"] for m in BENCH["end_to_end"]}


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_workload_files_exist(w):
    names = {c["name"] for c in BENCH["configs"]}
    assert w["config"] in names
    tr = cell.traffic(w["traffic"])
    assert tr["chains"] % w["chips"] == 0
    lim = cell.limits(w["name"])
    assert set(lim) == set(cell.numbers(__import__("numpy").zeros(4)))
    for name, entry in lim.items():
        assert entry["lower"] < entry["limit"] < entry["upper"], name


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_config_files(c):
    cfg, module = cell.config(BENCH, c["name"])
    assert cfg["name"] == c["name"]
    assert cfg["reduced"] == c["reduced"]
    assert c["file"].startswith(BENCH["paths"][0] + "/")
    for fn in ("program_target", "reference", "start_states", "work"):
        assert callable(getattr(module, fn))


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_metric_reader_matches(m):
    reader = cell.load_module(cell.HERE / "metrics" / f"{m['name']}.py")
    assert reader.LAYER == m["layer"]
    assert reader.MOVES == m["moves"] and m["moves"] in E2E
    cells = m.get("workloads", [w["name"] for w in BENCH["workloads"]])
    for name in cells:
        cell.entry(BENCH, name)  # the cell exists ...
    # ... and reports the end-to-end metric this one moves: every cell
    # reports draws_per_s and setup_s.
    assert m["moves"] in {"draws_per_s", "setup_s"}


def test_names_and_units():
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = set()
        for x in BENCH[group]:
            assert name.match(x["name"]) and x["name"] not in seen
            seen.add(x["name"])
            if "unit" in x:
                assert unit.match(x["unit"])
    assert {"draws_per_s", "setup_s"} <= E2E


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        cell.peaks("TPU v99")
    assert cell.peaks("TPU v5 lite")["flops_per_s"] == 197e12


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_peak_at_the_stated_precision(c):
    """A roofline or an mfu divides by the peak at the precision the
    configuration states: at 'highest' six bfloat16 passes a product."""
    cfg, _ = cell.config(BENCH, c["name"])
    pk = cell.peaks("TPU v5 lite")
    passes = {"default": 1, "high": 3, "highest": 6}[cfg["matmul_precision"]]
    assert cell.peak_flops(pk, cfg["matmul_precision"]) == 197e12 / passes
