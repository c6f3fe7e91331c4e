"""The comparison that decides `correct`, at sizes a CPU test can hold.

The program's own answers pass the cell's limits; the control (the
reference at bfloat16 in the program's place) fails them; and a run
whose timed path is broken underneath comes out not correct, once for
each fault a cell can have (`harness/faults.py`): a step that returns
its state unchanged, half of the tasks left out with the cost scaled up
from the rest, half of the tasks never updated with the cost reported as
it is, and an answer altered where it is produced.  (A one-chip cell has
no exchange between chips to leave out.)
"""
import time

import pytest

from harness import check, faults, registry
from harness.cell import readings, run_cell

SPEC = registry.load_benchmark()
CELLS = [w["name"] for w in SPEC["workloads"]]


def _cell(name):
    cell = registry.workload(SPEC, name)
    return (cell, registry.config(SPEC, cell["config"]),
            registry.traffic(cell["traffic"]))


@pytest.mark.parametrize("name", CELLS)
def test_program_passes_and_control_fails(name):
    cell, cfg, mix = _cell(name)
    limits = check.load_limits(name)
    for seed in (0, 1, 2):
        sound, ctrl = readings(cfg, mix, seed, 0.5)
        assert check.verdict(sound, limits)[0], sound
        assert not check.verdict(ctrl, limits)[0], ctrl


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", [None, "unchanged", "half", "half_frozen",
                                   "altered"])
def test_broken_timed_path_is_not_correct(name, fault, monkeypatch):
    if fault is not None:
        faults.plant({"half": "half_dropped"}.get(fault, fault),
                     monkeypatch.setattr)
    cell, cfg, mix = _cell(name)
    # seed 3: the frozen half of its tasks is far from optimal, as on
    # most seeds (PERF.md gives the readings over many)
    out = run_cell(SPEC, cell, cfg, mix, 3, 0.5, False, time.perf_counter(),
                   say=lambda *_: None)
    assert out["correct"] is (fault is None), out["checks"]
    assert list(out)[-1] == "checks"
    if fault == "half_frozen":
        gap = out["checks"]["task_gap"]
        assert gap["value"] > gap["limit"]
