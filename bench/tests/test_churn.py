"""The churn loop (`loops/churn.py`) on a CPU run of `geant.churn`: the
program's answers pass with nothing compiled inside the window, a traced
window carries the program's churn spans for the per-layer metrics, and
each fault planted under live churn comes out not correct
(`harness/churn_faults.py`, the warm-path ones with a sound cold start,
and the shared step fault of `harness/faults.py`)."""
import time
import types

import numpy as np
import pytest

from harness import churn_faults, faults, registry
from harness.cell import run_cell
from harness.churn_model import ChurnModel
from harness.deploy import make_deployment

SPEC = registry.load_benchmark()
CELL = registry.workload(SPEC, "geant.churn")
CFG = registry.config(SPEC, CELL["config"])
MIX = registry.traffic(CELL["traffic"])
CHURN_METRICS = ["apply_ms.churn", "repair_ms.churn", "rebaseline_ms.churn",
                 "host_syncs.churn"]


def _run(seed, seconds, traced=False):
    said = []
    out = run_cell(SPEC, CELL, CFG, MIX, seed, seconds, traced,
                   time.perf_counter(), say=said.append)
    return out, said


def test_warm_up_covers_every_kind_at_both_widths():
    loop = registry._module("loops", "churn", registry.BENCH)
    dep = make_deployment(CFG["deployment"],
                          registry.topology(CFG["deployment"]), 0)
    events = loop.warm_up_events(dep)
    assert {ev[0] for ev in events} == set(MIX["weights"])
    model, widths = ChurnModel(dep), []
    for ev in events:
        model.apply(ev)
        net = model.live()
        widths.append(int(np.bincount(net.src, minlength=net.V).max()))
    # GEANT's one degree-4 node: its failure or the cut of one of its
    # links narrows the slots to 3, and the caps allow nothing narrower
    assert sorted(set(widths)) == [3, 4]


def test_program_answers_pass_with_no_compile_in_window():
    out, said = _run(7, 2.0)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 10
    assert " 0 compiles inside the window" in said[0], said[0]
    assert set(out["metrics"]) == {"setup_s", "solve_s"}


def test_traced_window_reads_the_program_spans():
    out, _ = _run(7, 1.0, traced=True)
    for name in CHURN_METRICS:
        assert out["metrics"][name]["value"] > 0.0, name


def test_readers_read_nothing_without_program_spans():
    run = types.SimpleNamespace(window=types.SimpleNamespace())
    for name in CHURN_METRICS:
        assert registry.reader(name)(run) is None
    run.window.program = {"spans": {}, "counters": {"host_syncs": 5}}
    for name in CHURN_METRICS:
        assert registry.reader(name)(run) is None


@pytest.mark.parametrize("fault,shows_in", [
    ("old_recovery", "delivery_loss"),
    ("ignored", "cost_gap"),
    ("unchanged", "not_descended"),
    ("warm_unchanged", "rate_not_descended"),
    ("warm_half_frozen", "stalled_tasks"),
    ("warm_half_dropped", "cost_gap"),
])
def test_planted_fault_is_not_correct(fault, shows_in, monkeypatch):
    mod = churn_faults if fault in churn_faults.FAULTS else faults
    mod.plant(fault, monkeypatch.setattr)
    out, _ = _run(7, 2.0)
    assert not out["correct"], out["checks"]
    c = out["checks"][shows_in]
    assert c["value"] > c["limit"], out["checks"]
    if fault.startswith("warm_"):
        # planted after the cold solve: the window's answers catch it
        for name in ("task_gap", "not_descended"):
            c = out["checks"][name]
            assert c["value"] <= c["limit"], out["checks"]
