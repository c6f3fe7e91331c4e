"""Run the system's main path once on a TPU and check what comes out.

    python chip_smoke.py                # one chip: phases (a)-(d)
    python chip_smoke.py --four-chips   # four chips: the sharded paths only

One chip, through the entry points a user calls:

  (a) reference  solve the Table II row `connected_er` with `core.run`
                 and compare its cost with the convex flow-domain optimum
                 (`core.flow_domain_optimum`); check Theorem 1's residual.
  (b) scale      solve `sw_1000` and `ba_10000` (degree-bucketed) with
                 the sparse engine from the shortest-path φ⁰ for a fixed
                 number of iterations: costs finite, non-increasing,
                 below T⁰, and the final φ feasible.
  (c) churn      replay the canned `sw_1000_churn` schedule through the
                 fused stream (`ReplayEngine.play(stream=True)`).
  (d) routing    plan a multi-pod cluster with `RequestRouter` and serve
                 a few hundred `decide` calls from the live φ.

With --four-chips it runs only what exists across chips: `run_distributed`
on a 4-device task mesh at `sw_1000` against the single-device `run`, and
`node_flows_carry_and_cost` on a 2x2 (tasks x nodes) mesh against the
unsharded flows.

The script exits nonzero, with no result line, when JAX finds no TPU or
any check fails.  Times it prints are informational.  Its last line is
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import jax
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro import core  # noqa: E402
from repro.core.network import flows_carry_and_cost  # noqa: E402
from repro.kernels import ops as kernel_ops  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.serving import PodSpec, RequestRouter  # noqa: E402

# Phase (a): relative gap to the flow-domain optimum that a CPU run of
# the same solve reaches (JAX 0.9.0: 12.2160892 against 12.2160835), and
# the tolerance the chip is allowed beyond it: the f32 noise floor of the
# reference itself (evaluated on a TPU it stops 1e-5 higher).
REF_CPU_GAP = 5e-7
REF_TOL = 1e-5
THEOREM1_MAX = 0.05            # as tests/test_system.py holds abilene to
# --four-chips: relative agreement of sharded and single-device results
# (they differ only in cross-device summation order).
SHARD_RTOL = 1e-5


class CompileLog:
    """Counts compiles and persistent-cache hits from JAX's monitoring
    events (process-wide; register once)."""

    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return (self.compiles, self.compile_s, self.cache_hits)


def check(cond, msg: str) -> None:
    if not cond:
        raise RuntimeError(f"check failed: {msg}")


def check_descent(costs, what: str) -> None:
    c = np.asarray(costs, np.float64)
    check(np.isfinite(c).all(), f"{what}: non-finite cost")
    check((np.diff(c) <= 0.0).all(), f"{what}: cost increased")


def peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def run_phase(name: str, fn, log: CompileLog, *args, **kw):
    c0, s0, h0 = log.snapshot()
    t0 = time.perf_counter()
    out = fn(*args, **kw)
    wall = time.perf_counter() - t0
    c1, s1, h1 = log.snapshot()
    print(f"[{name}] ok  (informational: wall {wall:.1f} s, "
          f"{c1 - c0} compiles in {s1 - s0:.1f} s, {h1 - h0} cache hits, "
          f"device peak {peak_bytes()} B)", flush=True)
    return out


# ------------------------------------------------------------------ phases
def phase_reference(spec, n_iters: int = 200):
    net = core.make_scenario(spec)
    phi, hist = core.run(net, core.spt_phi(net), n_iters=n_iters)
    check_descent(hist["costs"], "reference solve")
    # the convex reference is a host (scipy) program: evaluate its cost
    # terms on the host's CPU backend, so it is the value a CPU run gets
    with jax.default_device(jax.devices("cpu")[0]):
        ref = core.flow_domain_optimum(core.make_scenario(spec))
    gap = hist["final_cost"] / ref - 1.0
    res = core.theorem1_residual(net, phi)
    print(f"  connected_er: SGP {hist['final_cost']:.7f}  flow-domain "
          f"optimum {ref:.7f}  gap {gap:+.3e}  theorem1 "
          f"{res['theorem1']:.3e}  loop_free {res['loop_free']}")
    check(-REF_TOL <= gap <= REF_CPU_GAP + REF_TOL,
          f"gap {gap:+.3e} to the flow-domain optimum outside "
          f"[-{REF_TOL}, {REF_CPU_GAP + REF_TOL}]")
    check(res["theorem1"] < THEOREM1_MAX,
          f"theorem1 residual {res['theorem1']}")
    check(res["loop_free"], "reference φ has a loop")


def phase_solve(spec, n_iters: int, bucketed: bool):
    net = core.make_scenario(spec)
    nbrs = core.build_neighbors(net.adj)
    phi0 = core.spt_phi_sparse(net, nbrs)
    phi, hist = core.run(net, phi0, n_iters=n_iters, method="sparse",
                         bucketed=bucketed)
    costs = hist["costs"]
    check_descent(costs, f"V={net.V} solve")
    check(costs[-1] < costs[0], f"V={net.V}: final cost not below T0")
    core.check_feasible(phi, nbrs, dest=net.dest)
    print(f"  V={net.V} S={net.S} Dmax={nbrs.Dmax} bucketed={bucketed}: "
          f"T0 {costs[0]:.4f} -> {costs[-1]:.4f} over {n_iters} "
          f"iterations ({hist['n_rejected']} rejected)")


def phase_churn(spec, schedule: str, tail_iters: int = 5):
    net = core.make_scenario(spec)
    sched = core.churn_schedule(schedule, net)
    eng = core.ReplayEngine(net)
    hist = eng.play(sched, tail_iters=tail_iters, stream=True)
    check(np.isfinite(hist["costs"]).all(), "churn: non-finite cost")
    for rec in hist["records"]:
        check_descent([rec.cost_after] + rec.segment_costs,
                      f"churn segment after {type(rec.event).__name__}")
    core.check_feasible(eng.phi, eng.nbrs, dest=eng.net.dest)
    print(f"  {sched.name}: {sched.n_events} events over "
          f"{hist['n_iters']} iterations, final cost "
          f"{hist['final_cost']:.4f}")


def phase_routing(n_requests: int = 300, seed: int = 0):
    pods = [PodSpec(30.0), PodSpec(20.0, speed=0.8),
            PodSpec(40.0, speed=1.2), PodSpec(25.0)]
    classes = {"chat": 1.5, "summarize": 0.3, "embed": 0.05}
    demand = np.array([[2.0, 1.0], [1.0, 2.0], [0.5, 0.8]])
    router = RequestRouter(pods, n_frontends=2, classes=classes,
                           demand=demand)
    summary = router.plan()
    check_descent(router.history["costs"], "router plan")
    core.check_feasible(router.phi, router.nbrs, dest=router.net.dest)
    dispatch = summary["dispatch"]              # [class, pod] workload
    rng = np.random.RandomState(seed)
    names = list(classes)
    counts = np.zeros(len(pods), np.int64)
    for _ in range(n_requests):
        s, f = rng.randint(len(names)), rng.randint(router.F)
        pod = router.decide(names[s], f, rng=rng)
        check(0 <= pod < len(pods), f"decide returned pod {pod}")
        check(dispatch[s, pod] > 0.0,
              f"class {names[s]} sent to pod {pod}, which its plan "
              "gives no work")
        counts[pod] += 1
    print(f"  {n_requests} decisions from the live φ, per pod "
          f"{counts.tolist()}; plan cost {summary['total_cost']:.4f}")


def _sw_start(spec):
    net = core.make_scenario(spec)
    nbrs = core.build_neighbors(net.adj)
    return net, nbrs, core.spt_phi_sparse(net, nbrs)


def _spread_over(arr, n: int, what: str) -> None:
    placed = {s.device for s in arr.addressable_shards}
    check(len(placed) == n, f"{what} on {len(placed)} device(s), not {n}")


def shard_tasks(spec, n_iters: int):
    """`run_distributed` on a 4-device task mesh against `run`."""
    net, _, phi0 = _sw_start(spec)
    phi_d, hist_d = core.run_distributed(net, phi0, n_iters=n_iters,
                                         mesh=core.task_mesh(4),
                                         method="sparse")
    _spread_over(phi_d.data, 4, "φ carry")
    _, hist_1 = core.run(net, phi0, n_iters=n_iters, method="sparse")
    c_d, c_1 = (np.asarray(h["costs"]) for h in (hist_d, hist_1))
    check_descent(c_d, "run_distributed")
    n = min(len(c_d), len(c_1))
    rel = float(np.max(np.abs(c_d[:n] - c_1[:n]) / c_1[:n]))
    print(f"  run_distributed (4 task shards) vs run: final {c_d[-1]:.6f} "
          f"vs {c_1[-1]:.6f}, max rel diff {rel:.2e} over {n} costs")
    check(len(c_d) == len(c_1), "accepted-step counts differ")
    check(rel <= SHARD_RTOL, f"costs differ by {rel:.2e}")


def shard_nodes(spec):
    """`node_flows_carry_and_cost` on a 2x2 mesh against the unsharded
    flows."""
    net, nbrs, phi0 = _sw_start(spec)
    carry, cost = core.node_flows_carry_and_cost(
        net, phi0, nbrs, core.task_node_mesh(2, 2))
    _spread_over(carry.t_data, 4, "node-sharded carry")
    ref, ref_cost = flows_carry_and_cost(net, phi0, "sparse", nbrs=nbrs)
    worst = 0.0
    for f in ("t_data", "t_result", "F", "G"):
        a, b = (np.asarray(getattr(x, f)) for x in (carry, ref))
        worst = max(worst, float(np.max(np.abs(a - b))
                                 / max(np.max(np.abs(b)), 1e-30)))
    crel = abs(float(cost) - float(ref_cost)) / float(ref_cost)
    print(f"  node_flows_carry_and_cost (2x2) vs unsharded: flows max rel "
          f"diff {worst:.2e}, cost rel diff {crel:.2e}")
    check(worst <= SHARD_RTOL and crel <= SHARD_RTOL,
          "node-sharded flows differ from the unsharded solve")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded paths, on 4 devices")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1

    cache_dir = enable_compile_cache()
    n_cached = (len(os.listdir(cache_dir)) if os.path.isdir(cache_dir)
                else 0)
    print(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
          f"compile cache {cache_dir} ({n_cached} entries)")
    print("impl per op: " + ", ".join(
        f"{op}={kernel_ops.default_impl(op)}"
        for op in ("edge_rounds", "edge_rounds_bucketed",
                   "simplex_project")))
    log = CompileLog()
    spec = core.TABLE_II

    if args.four_chips:
        check(len(jax.devices()) >= 4,
              f"--four-chips needs 4 devices, found {len(jax.devices())}")
        run_phase("4-chip task mesh", shard_tasks, log, spec["sw_1000"],
                  n_iters=20)
        run_phase("4-chip tasks x nodes mesh", shard_nodes, log,
                  spec["sw_1000"])
    else:
        run_phase("a reference", phase_reference, log,
                  spec["connected_er"])
        run_phase("b solve sw_1000", phase_solve, log, spec["sw_1000"],
                  n_iters=30, bucketed=False)
        run_phase("b solve ba_10000", phase_solve, log, spec["ba_10000"],
                  n_iters=20, bucketed=True)
        run_phase("c churn", phase_churn, log, spec["sw_1000"],
                  "sw_1000_churn")
        run_phase("d routing", phase_routing, log)

    n_after = (len(os.listdir(cache_dir)) if os.path.isdir(cache_dir)
               else 0)
    print(f"compile cache: {n_cached} -> {n_after} entries; "
          f"{log.cache_hits} hits over {log.compiles} compiles")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
