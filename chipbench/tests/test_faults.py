"""A run whose timed path is broken underneath reads ``correct: false``;
the same run unbroken reads true.  The control (the reference one
precision lower, in the program's place) fails the same limits.

Everything here runs on the CPU at a size a test can hold: eight chains,
trees of depth at most 4, and a smaller density.  A fault in one chain
runs at the cells' own 1024 chains.  The checks and limits are the
cells' own.
"""
import numpy as np
import pytest

import cell
import run

SMALL = {
    "logreg-1024": {"num_data": 300, "dim": 6, "max_tree_depth": 4},
    "corrgauss-1024": {"dim": 10, "max_tree_depth": 4},
}
TRAFFIC = {"chains": 8, "compare_calls": 2}


class Broken:
    """The program's kernel with one fault planted where it produces."""

    def __init__(self, kernel, fault: str, traj: int):
        self.kernel, self.fault, self.traj = kernel, fault, traj

    @property
    def last_result(self):
        return self.kernel.last_result

    def unchanged(self, theta):
        theta = np.asarray(theta)
        return {"theta": theta, "sum_theta": self.traj * theta,
                "sum_sq": self.traj * theta * theta}

    def __call__(self, theta, eps, keys):
        out = {k: np.asarray(v) for k, v in
               self.kernel(theta, eps, keys).items()}
        if self.fault == "unchanged":
            return self.unchanged(theta)
        if self.fault == "half_batch":
            half = len(out["theta"]) // 2
            same = self.unchanged(theta)
            return {k: np.concatenate([v[:half], same[k][half:]])
                    for k, v in out.items()}
        if self.fault == "altered":
            theta = out["theta"]
            return {**out, "theta": theta + 0.01 * theta.std(axis=0)}
        if self.fault == "one_lane_unchanged":
            same = self.unchanged(theta)
            return {k: np.concatenate([v[:-1], same[k][-1:]])
                    for k, v in out.items()}
        if self.fault == "one_lane_altered":
            theta = out["theta"].copy()
            theta[-1] += 0.1 * theta.std(axis=0)
            return {**out, "theta": theta}
        raise ValueError(self.fault)


FAULTS = [(c, f) for c in SMALL
          for f in ("unchanged", "half_batch", "altered")]
#: Faults in one chain, at the cells' own chain count.
ONE_LANE = [(c, f) for c in SMALL
            for f in ("one_lane_unchanged", "one_lane_altered")]


def _run(name, fault=None, chains=TRAFFIC["chains"]):
    args = run.parse(["--workload", name, "--seed", "3000000017",
                      "--seconds", "0.5", "--trace", "0"])

    def plant(c):
        c.kernel = Broken(c.kernel, fault, c.traj)

    return run.run(args, require_tpu=False,
                   traffic_over={**TRAFFIC, "chains": chains},
                   config_over=SMALL[name], plant=plant if fault else None)


@pytest.mark.parametrize("name", list(SMALL))
def test_sound_run_is_correct(name):
    res = _run(name)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0


@pytest.mark.parametrize("name,fault", FAULTS)
def test_broken_run_is_not_correct(name, fault):
    res = _run(name, fault)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("name,fault", ONE_LANE)
def test_one_broken_chain_is_not_correct(name, fault):
    """One chain of the cell's own 1024 is broken: only the widest gap
    sees it, and that is enough."""
    chains = cell.traffic(cell.entry(cell.benchmark(), name)["traffic"])[
        "chains"]
    res = _run(name, fault, chains=chains)
    checks = res["checks"]
    assert checks["gap_p90"]["value"] <= checks["gap_p90"]["limit"], checks
    assert checks["gap_max"]["value"] > checks["gap_max"]["limit"], checks
    assert not res["correct"], checks


#: The control's rounding grows with the density's size: logreg keeps its
#: full data here (16 chains), the Gaussian its small size.
CONTROL = {**SMALL, "logreg-1024": {}}


@pytest.mark.parametrize("name", list(SMALL))
def test_control_is_not_correct(name):
    """The reference one precision lower (bfloat16 for corrgauss, three
    bfloat16 passes for logreg's 'highest'), put in the program's place
    on the program's own inputs, fails at least one of the cell's
    limits."""
    run.enable_cache()
    bench = cell.benchmark()
    c = cell.build(bench, name, 11, traffic_over={**TRAFFIC, "chains": 16},
                   config_over=CONTROL[name])
    clock = cell.CompileClock()
    cell.warm_up(c)
    win = cell.run_window(c, 0.5, clock)
    sample = cell.fetch(c, cell.sample_calls(win, 11, 2))
    ref = cell.reference_outputs(c, sample, cell.reference_runner(c))
    low = cell.reference_outputs(c, sample,
                                 cell.reference_runner(c, control=True))
    gaps = np.concatenate([cell.chain_gaps(lo, r) for lo, r in zip(low, ref)])
    ok, checks = cell.judge(cell.numbers(gaps), cell.limits(name))
    assert not ok, checks
