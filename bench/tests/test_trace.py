"""The trace reduction and the roofline byte count."""
import gzip
import json
from pathlib import Path

import pytest

from harness import trace as tr
from harness.roofline import qp_bytes_per_iteration

RECORDED = Path(__file__).parent / "data" / "geant_solve_trace.json.gz"


def _op(dev, s, d, name="fusion.1"):
    return tr.Op(dev, float(s), float(d), name, "jit_step")


def _trace():
    dev = "/device:TPU:0"
    ops = [_op(dev, 100, 50), _op(dev, 120, 60),      # overlap: 100-180
           _op(dev, 300, 100, "simplex_project.2"),
           _op(dev, 900, 300),                        # runs past the window
           _op(dev, 10, 20)]                          # before the window
    spans = [tr.Span(50, 950, "window"), tr.Span(60, 200, "run"),
             tr.Span(400, 400, "seed")]
    return tr.Trace(ops, spans)


def _recorded():
    d = json.loads(gzip.decompress(RECORDED.read_bytes()))
    return tr.Trace([tr.Op(*o) for o in d["ops"]],
                    [tr.Span(s, dur, name) for name, s, dur in d["spans"]])


def test_busy_union_and_window():
    t = _trace()
    assert tr.window_s(t) == pytest.approx(950e-9)
    # union inside [50, 1000): 100-180, 300-400, 900-1000
    assert tr.busy_s(t) == pytest.approx(280e-9)


def test_idle_gaps_by_host_span():
    idle = tr.idle_by_span(_trace())
    # gaps: 50-100 (mid 75, in "run"), 180-300 (mid 240, in "run"),
    # 400-900 (mid 650, in "seed")
    assert idle == {"run": pytest.approx(170e-9),
                    "seed": pytest.approx(500e-9)}


def test_named_time_and_top_ops():
    t = _trace()
    assert tr.named_time_s(t, "simplex_project") == pytest.approx(100e-9)
    assert tr.named_time_s(t, "simplex") == 0.0
    top = tr.top_ops(t)
    assert top[0] == ["jit_step/fusion.1", pytest.approx(410e-9)]


def test_busy_is_averaged_over_chips():
    t = _trace()
    t.ops.append(_op("/device:TPU:1", 100, 400))
    assert tr.busy_s(t) == pytest.approx((280e-9 + 400e-9) / 2)


def test_op_and_module_names_from_events():
    assert tr.op_name("%simplex_project.2 = f32[1024,128]{1,0} custom-call("
                      "f32[1024,128]{1,0} %pad.56)") == "simplex_project.2"
    assert tr.module_name("jit__sgp_step_flows_impl(9881573927361158639)") \
        == "jit__sgp_step_flows_impl"


def test_recorded_chip_trace():
    t = _recorded()
    window = tr.window_s(t)
    busy = tr.busy_s(t)
    assert window == pytest.approx(0.030)
    assert 0.0 < busy < window
    idle = tr.idle_by_span(t)
    assert set(idle) == {"solve"}
    assert busy + idle["solve"] == pytest.approx(window)
    # the QP kernel: two calls per SGP iteration, each a custom call
    # named after the jitted `simplex_project`
    t0, t1 = t.window()
    qp = [o for o in t.ops if o.name.startswith("simplex_project.")
          and t0 <= o.start < t1]
    assert len(qp) >= 2 and len(qp) % 2 == 0
    assert tr.named_time_s(t, "simplex_project") == pytest.approx(
        sum(o.dur for o in qp) / 1e9)
    assert {o.module for o in qp} == {"jit__sgp_step_flows_impl"}
    top = tr.top_ops(t)
    assert len(top) == 10 and all(" = " not in k for k, _ in top)
    assert [v for _, v in top] == sorted((v for _, v in top), reverse=True)


def test_qp_bytes():
    # data rows [S*V, D+1] and result rows [S*V, D]: 3 float32 reads, a
    # one-byte mask and a float32 write per element
    assert qp_bytes_per_iteration(2, 3, 4) == (2 * 3 * 5 + 2 * 3 * 4) * 17
