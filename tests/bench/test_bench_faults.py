"""What decides ``correct`` has to fail a broken program and the control.

Each fault breaks the timed path underneath the harness (the compiled
chunk runner the window drives), and a run through ``harness.run_cell``
(the command looks for a chip before it) has to come out not correct.
The control is the plain reference computed in bfloat16, the precision
below the configuration's float32, put in the program's place: the
comparison has to fail it too. A one-chip cell has no exchange between chips, so that
fault has no case here.
"""

import dataclasses
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench import harness, reference, twin_cache  # noqa: E402
from test_bench_harness import throwaway_root  # noqa: E402


def _break(kind, run):
    """The runner ``run`` with one fault planted where it produces."""

    def broken(params, state, carries, week, route):
        new, carries, hist, dailies = run(params, state, carries, week, route)
        if kind == "state_unchanged":
            new = state
        elif kind == "half_batch_left_out":
            # the second half of the scenarios is never advanced
            B = new.day.shape[0]
            new = type(new)(**{f: getattr(new, f).at[B // 2:].set(
                getattr(state, f)[B // 2:]) for f in dataclasses.asdict(new)})
            hist = {k: v if k == "day" else v.at[:, B // 2:].set(0)
                    for k, v in hist.items()}
        elif kind == "arms_swapped":
            # the first two scenarios' outputs written to each other's slot
            swap = lambda x: x.at[0].set(x[1]).at[1].set(x[0])
            new = type(new)(**{f: swap(getattr(new, f))
                               for f in dataclasses.asdict(new)})
            hist = {k: v if k == "day" else v.at[:, 0].set(v[:, 1])
                    .at[:, 1].set(v[:, 0]) for k, v in hist.items()}
        elif kind == "answer_altered":
            # one scenario-day's new infections reported wrong
            hist = dict(hist)
            hist["new_infections"] = hist["new_infections"].at[0, 0].set(
                2 * hist["new_infections"][0, 0] + 1)
        else:
            raise ValueError(kind)
        return new, carries, hist, dailies

    return broken


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return throwaway_root(str(tmp_path_factory.mktemp("checkout")),
                          config="md-tti")


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch_left_out",
                                  "arms_swapped", "answer_altered"])
def test_fault_is_not_correct(root, kind, monkeypatch):
    from repro.engine.core import EngineCore

    runner_fn = EngineCore.runner_fn
    monkeypatch.setattr(
        EngineCore, "runner_fn",
        lambda self, days, obs=(): _break(kind, runner_fn(self, days, obs)))
    run, line = harness.run_cell(root, "tiny.light", 2 ** 31 + 99, 0.2,
                                 False, t_start=time.perf_counter())
    assert line["correct"] is False
    failed = [k for k, c in line["checks"].items() if c["value"] > c["limit"]]
    assert failed, line["checks"]


def test_control_is_not_correct(root):
    """The reference in bfloat16 against the reference in float32, over
    the same stretches a run compares (the advance and one chunk)."""
    cell = harness.load_cell(root, "tiny.light")
    cfg, traffic = cell.config, cell.traffic
    pop, _ = twin_cache.load_or_build(root, cfg["dataset"], cfg["block_size"])
    twin = harness.raw_twin(pop)
    seed = 2 ** 31 + 7
    out = {dtype: reference.stretches(cfg, traffic, twin, seed, dtype)
           for dtype in ("float32", "bfloat16")}
    numbers = reference.compare(out["bfloat16"], out["float32"])
    assert numbers["state_mismatch"] > 0
    failed = [k for k in cell.limits
              if numbers[k] > cell.limits[k]["limit"]]
    assert failed, numbers
    same = reference.compare(out["float32"], out["float32"])
    assert all(v == 0 for v in same.values())
    assert np.asarray(out["float32"][-1][1]["cumulative"]).max() > 0
