"""The md-tti arms with a test budget that binds, on the CPU.

``md-tti.surge`` runs 16,500 tests a day on the md twin (0.3% of its
people) under surge traffic, where the symptomatic far outnumber the
budget: the per-slot order statistic (``Topology.rank_threshold``) then
really selects. This is the same deployment on twin-2k, with the budget
scaled to 0.3% of its people and 1% of them seeded a day for a week,
through the harness and the plain reference, on the ``pallas-compact``
backend (the kernel's traced launcher, in interpret mode).
"""

import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402
from test_bench_harness import _write, throwaway_root  # noqa: E402

PEOPLE = 2000
BUDGET = PEOPLE * 3 // 1000  # 0.3% of people, as md-tti's 16,500 of 5.513M


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = throwaway_root(str(tmp_path_factory.mktemp("checkout")),
                          backend="pallas-compact", config="md-tti")
    path = os.path.join(root, "bench", "configs", "tiny.json")
    cfg = json.load(open(path))
    for arm in cfg["arms"]:
        for iv in arm["interventions"]:
            iv["tests_per_day"] = BUDGET
    _write(path, cfg)
    return root


def test_budget_binds_at_surge(root, monkeypatch):
    from repro.engine.core import EngineCore

    hists = []
    runner_fn = EngineCore.runner_fn

    def recording(self, days, obs=()):
        runner = runner_fn(self, days, obs)

        def run(*args):
            out = runner(*args)
            hists.append(out[2])
            return out

        return run

    monkeypatch.setattr(EngineCore, "runner_fn", recording)
    run, line = harness.run_cell(root, "tiny.light", 2 ** 31 + 1616, 0.2,
                                 False, t_start=time.perf_counter())
    assert line["correct"] is True, line["checks"]
    assert run.compared["state_mismatch"] == 0.0
    assert run.compared["stat_gap"] == 0.0

    cfg = run.cell.config
    arms = [a["name"] for a in cfg["arms"]]
    assert arms == ["none", "tti", "tti-no-trace"]
    assert len(hists) >= 2  # the advance, then the window's chunks
    window = {k: np.asarray(v) for k, v in hists[-1].items()}
    assert list(window["day"][:, 0]) == list(range(7, 14))
    tests = window["tests_used"]
    assert (tests[:, 0] == 0).all()
    for a in (arms.index("tti"), arms.index("tti-no-trace")):
        assert (tests[:, a] == BUDGET).all(), tests[:, a]
    assert window["traced"][:, arms.index("tti")].sum() > 0
    assert (window["traced"][:, arms.index("tti-no-trace")] == 0).all()
