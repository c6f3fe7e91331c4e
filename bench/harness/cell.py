"""One run of one cell: set-up, warm-up, the measured window (traced or
not), the metrics, and the check.  `bench/run.py` is the command line
around `run_cell`."""
from __future__ import annotations

import gc
import shutil
import tempfile
import time
import types

import jax

from . import check, deploy, registry
from .spans import CompileLog, Spans
from .peaks import peaks
from . import trace as tr

_LOG = None          # JAX's monitoring listeners are process-wide
# a traced run records at most this much of its window: the trace of a
# longer one only grows, and its per-layer shares are already steady
TRACE_SECONDS = 10.0


def compile_log() -> CompileLog:
    global _LOG
    if _LOG is None:
        _LOG = CompileLog()
    return _LOG


def _setup(cfg: dict, mix: dict, seed: int, spans, bench):
    """The deployment and the cell's loop, set up and warmed up."""
    seed = int(seed) % (1 << 64)
    dep_cfg = cfg["deployment"]
    dep = deploy.make_deployment(dep_cfg,
                                 registry.topology(dep_cfg, bench), seed)
    drv = registry.loop(mix["loop"], bench)(cfg, mix, dep, seed, spans)
    drv.warm_up()
    return dep, drv



def readings(cfg: dict, mix: dict, seed: int, seconds: float,
             bench=registry.BENCH):
    """(program's numbers, control's numbers) of one seed's window."""
    dep, drv = _setup(cfg, mix, seed, Spans(), bench)
    answers = drv.window(seconds, compile_log()).answers
    loop_cls = type(drv)
    del drv
    gc.collect()
    return (loop_cls.check(dep, answers),
            loop_cls.check(dep, answers, control=True))


def run_cell(spec: dict, cell: dict, cfg: dict, mix: dict, seed: int,
             seconds: float, traced: bool, t_start: float,
             limits: dict | None = None, say=print,
             bench=registry.BENCH) -> dict:
    """Run the cell once and return the result line's object."""
    log = compile_log()
    spans = Spans(annotate=traced)
    dep, drv = _setup(cfg, mix, seed, spans, bench)
    spans.clear()
    jax.effects_barrier()
    setup_s = time.perf_counter() - t_start

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    if traced:
        seconds = min(seconds, TRACE_SECONDS)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with spans("window"):
            win = drv.window(seconds, log)
    finally:
        if traced:
            jax.profiler.stop_trace()
    dev = jax.devices()[0]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:int(cell["chips"])])
    lat = sorted(win.latencies)
    say(f"window: {len(lat)} operations in {win.seconds:.3f} s (each "
        f"{1e3 * lat[0]:.3f} to {1e3 * lat[-1]:.3f} ms, median "
        f"{1e3 * lat[len(lat) // 2]:.3f}); {win.compiles} compiles inside "
        f"the window; set-up {setup_s:.3f} s ({log.cache_hits} "
        "compile-cache hits)")
    trace = None
    if traced:
        trace = tr.load(tr.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)

    run = types.SimpleNamespace(
        loop=mix["loop"], window=win, setup_s=setup_s,
        spans=dict(spans.seconds), trace=trace, memory_peak_bytes=peak,
        peaks=peaks(dev.device_kind) if dev.platform == "tpu" else None,
        dep=dep, cfg=cfg)
    kind = "per_layer" if traced else "end_to_end"
    metrics = {}
    for m in registry.metrics_for(spec, cell["name"], kind):
        v = registry.reader(m["name"], bench)(run)
        if v is None:
            if kind == "end_to_end":
                raise RuntimeError(f"end-to-end metric {m['name']} "
                                   "read nothing")
            continue
        metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    breakdown = None
    if traced:
        device["busy_s"] = tr.busy_s(trace)
        device["window_s"] = tr.window_s(trace)
        idle = tr.idle_by_span(trace) if trace.devices else {}
        breakdown = {"device_ops": tr.top_ops(trace),
                     "idle_gaps": [[k, v] for k, v in sorted(
                         idle.items(), key=lambda kv: -kv[1])[:10]]}

    # the check: after the window, with the program's arrays released
    answers = win.answers
    loop_cls = type(drv)
    del drv, run, trace
    gc.collect()
    numbers = loop_cls.check(dep, answers)
    if limits is None:
        limits = check.load_limits(cell["name"], bench)
    correct, rows = check.verdict(numbers, limits)
    shown = {k: v for k, v in numbers.items() if k not in limits}
    say("numbers not compared: " + ", ".join(
        f"{k} {v:.6g}" for k, v in shown.items()))
    out = {"correct": bool(correct), "attempted": len(win.latencies),
           "failed": 0, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": v, "limit": lim} for k, v, lim in rows}
    return out

