"""Invariant/property layer for the streaming churn replay subsystem.

The replay engine's contract (core.replay) is locked four ways:

* **Property loop** — a seeded randomized 20-event schedule on a small
  Table II scenario; after EVERY event the live iterate must be
  feasible (data rows on the simplex, result rows simplex-or-empty,
  exactly zero mass on dead slots), loop-free, and every inter-event
  segment's accepted-cost sequence monotone non-increasing (cost
  recovers monotonically after each shock).

* **Warm-start parity** — a zero-event replay and chunked
  `init_run_state`/`run_chunk` driving must match one uninterrupted
  `run(method="sparse")` BITWISE (the tests/test_phi_sparse.py parity
  convention extended to the resumable driver state), for both the
  single-process and the shard_mapped driver.

* **Recovery regression** — a node that fails and then RECOVERS must
  keep the warm iterate: only rows that lost mass are rebuilt from the
  SPT, and the recovered node's empty rows take their SPT rows (dense
  `refeasibilize` as the bitwise oracle).  Before the damaged-row fix,
  a recovery reset every task to the SPT tree; before the empty-row
  fill, the recovered node's results were dropped
  (`test_geant_churn_delivers_every_result`).

* **Scale** (slow) — the canned `sw_1000_churn` schedule end-to-end,
  with per-event invariants and warm-start beating the cold restart.

* **Round-trip identities** — cut-then-restore / fail-then-recover
  with ZERO intervening iterations on a crafted 6-node instance where
  the repaired iterate is predictable in closed form: the cut of a
  mass-free edge round-trips `refeasibilize_sparse` bitwise to φ⁰, and
  so does a leaf node's fail/recover (its result row comes back as
  its SPT row, which is φ⁰'s).
"""
import numpy as np
import pytest

from repro import core
from repro.core.replay import check_invariants

_CACHE = {}


def _setup(name):
    if name not in _CACHE:
        net = core.make_scenario(core.TABLE_II[name])
        _CACHE[name] = (net, core.build_neighbors(net.adj))
    return _CACHE[name]


# ---------------------------------------------------------- churn algebra
def test_churn_state_single_failure_matches_fail_node():
    """One NodeFail folded through ChurnState reproduces fail_node
    exactly — the replay engine's event semantics are the paper's."""
    net, _ = _setup("abilene")
    st = core.ChurnState(net)
    assert st.apply(core.NodeFail(3)) == "topology"
    got = st.network()
    want = core.fail_node(net, 3)
    for f in ("adj", "r", "dest"):
        np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                      np.asarray(getattr(want, f)), f)
    np.testing.assert_array_equal(np.asarray(got.comp_cost.params),
                                  np.asarray(want.comp_cost.params))


def test_churn_state_failure_recovery_is_exact_inverse():
    """fail -> recover restores the pristine network bit-for-bit
    (links, capacity, rates, destinations) — the property `fail_node`
    alone cannot provide (it destroys the pre-failure state)."""
    net, _ = _setup("abilene")
    st = core.ChurnState(net)
    st.apply(core.NodeFail(3))
    st.apply(core.LinkCut(0, 1))
    st.apply(core.NodeRecover(3))
    st.apply(core.LinkRestore(0, 1))
    got = st.network()
    for f in ("adj", "r", "dest"):
        np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                      np.asarray(getattr(net, f)), f)
    np.testing.assert_array_equal(np.asarray(got.comp_cost.params),
                                  np.asarray(net.comp_cost.params))


def test_random_schedule_is_self_consistent():
    """Seeded schedules only recover failed nodes / restore cut links,
    never fail destinations — including destinations MOVED by a
    generated DestRedraw — and are reproducible per seed."""
    net, _ = _setup("fog")
    s1 = core.random_schedule(net, 30, seed=5)
    s2 = core.random_schedule(net, 30, seed=5)
    assert s1 == s2
    dest_of = {s: int(d) for s, d in enumerate(np.asarray(net.dest))}
    failed, cut = set(), set()
    for _, ev in s1.events:
        if isinstance(ev, core.NodeFail):
            assert ev.node not in failed
            assert ev.node not in set(dest_of.values())
            failed.add(ev.node)
        elif isinstance(ev, core.NodeRecover):
            assert ev.node in failed
            failed.discard(ev.node)
        elif isinstance(ev, core.LinkCut):
            cut.add((ev.u, ev.v))
        elif isinstance(ev, core.LinkRestore):
            assert (ev.u, ev.v) in cut
            cut.discard((ev.u, ev.v))
        elif isinstance(ev, core.DestRedraw):
            # generated redraws carry an explicit, never-failed target
            assert ev.node is not None and ev.node not in failed
            dest_of[ev.task] = ev.node
    st = core.ChurnState(net)
    for _, ev in s1.events:
        st.apply(ev)
    # the ChurnState's final destinations match the generator's book
    np.testing.assert_array_equal(
        st.dest, [dest_of[s] for s in range(net.S)])


@pytest.mark.parametrize("seed", [2, 3, 5, 6])
def test_random_schedule_never_disconnects_sources(seed):
    """After EVERY prefix of a generated schedule, every live exogenous
    source still reaches its task's destination — generated churn never
    silently turns flows undeliverable (these seeds used to leave 3-12
    dark source rows before the generator grew its connectivity guard)."""
    from repro.core.events import _reaches
    net, _ = _setup("abilene")
    sched = core.random_schedule(net, 20, seed=seed)
    st = core.ChurnState(net)
    for _, ev in sched.events:
        st.apply(ev)
        cur = st.network()
        adj = np.asarray(cur.adj)
        r = np.asarray(cur.r)
        dests = np.asarray(cur.dest)
        for s in range(net.S):
            srcs = np.nonzero(r[s] > 0.0)[0]
            assert _reaches(adj, srcs, int(dests[s])), \
                (ev, s, srcs, int(dests[s]))


def test_schedule_orders_events():
    """Out-of-order schedules are refused up front; ties (two events at
    the SAME iteration) are legal — they apply back-to-back with a
    zero-length segment whose attribution is locked by
    tests/test_replay_stream.py."""
    with pytest.raises(ValueError):
        core.ChurnSchedule(((5, core.NodeFail(1)), (4, core.LinkCut(0, 2))))
    sched = core.ChurnSchedule(((5, core.NodeFail(1)),
                                (5, core.LinkCut(0, 2))))
    assert sched.n_events == 2 and sched.horizon == 5


# ------------------------------------------------------ warm-start parity
def test_zero_event_replay_is_bitwise_run():
    """The engine adds NOTHING to an uninterrupted run: a replay with
    zero events walks run(method='sparse')'s exact trajectory."""
    net, nbrs = _setup("abilene")
    phi0 = core.spt_phi(net)
    want_phi, want = core.run(net, phi0, n_iters=10, method="sparse")
    eng = core.ReplayEngine(net, phi0=core.phi_to_sparse(phi0, nbrs))
    hist = eng.play(core.ChurnSchedule((), name="empty"), tail_iters=10)
    np.testing.assert_array_equal(np.asarray(want["costs"]),
                                  np.asarray(hist["costs"]))
    back = core.sparse_to_phi(eng.phi, eng.nbrs, net.V)
    np.testing.assert_array_equal(np.asarray(back.data),
                                  np.asarray(want_phi.data))
    np.testing.assert_array_equal(np.asarray(back.result),
                                  np.asarray(want_phi.result))


def test_chunked_run_state_is_bitwise_run():
    """init_run_state + arbitrary chunking == one run call, bitwise."""
    net, nbrs = _setup("abilene")
    phi0 = core.spt_phi(net)
    want_phi, want = core.run(net, phi0, n_iters=12, method="sparse")
    st = core.init_run_state(net, phi0, method="sparse")
    for n in (1, 4, 0, 7):
        core.run_chunk(net, st, n)
    assert st.it == 12
    np.testing.assert_array_equal(np.asarray(want["costs"]),
                                  np.asarray(st.costs))
    back = core.sparse_to_phi(st.phi, st.nbrs, net.V)
    np.testing.assert_array_equal(np.asarray(back.data),
                                  np.asarray(want_phi.data))


def test_chunked_run_honors_early_stop():
    """A chunk that hits the tol early-exit marks the state stopped;
    further chunks are no-ops, so chunked-with-tol still matches the
    uninterrupted run bitwise instead of iterating past the exit."""
    net, _ = _setup("abilene")
    phi0 = core.spt_phi(net)
    _, want = core.run(net, phi0, n_iters=30, method="sparse", tol=1e-3)
    assert len(want["costs"]) < 31          # the tol exit actually fired
    st = core.init_run_state(net, phi0, method="sparse")
    for n in (10, 10, 10):
        core.run_chunk(net, st, n, tol=1e-3)
    assert st.stopped
    np.testing.assert_array_equal(np.asarray(want["costs"]),
                                  np.asarray(st.costs))


def test_chunked_distributed_state_is_bitwise_run_distributed():
    """The shard_mapped driver is chunkable the same way."""
    net, nbrs = _setup("fog")
    phi0 = core.spt_phi(net)
    _, want = core.run_distributed(net, phi0, n_iters=8, method="sparse")
    st = core.init_distributed_state(net, phi0, method="sparse")
    for n in (3, 5):
        core.run_distributed_chunk(st, n)
    np.testing.assert_array_equal(np.asarray(want["costs"]),
                                  np.asarray(st.costs))


def test_distributed_replay_zero_event_parity():
    """driver='distributed' zero-event replay == run_distributed."""
    net, nbrs = _setup("fog")
    sp0 = core.spt_phi_sparse(net, nbrs)
    _, want = core.run_distributed(net, sp0, n_iters=6, method="sparse")
    eng = core.ReplayEngine(net, phi0=sp0, driver="distributed")
    hist = eng.play(core.ChurnSchedule((), name="empty"), tail_iters=6)
    np.testing.assert_array_equal(np.asarray(want["costs"]),
                                  np.asarray(hist["costs"]))


def test_distributed_rate_event_reuses_compiled_step():
    """A rate-only churn event swaps the churned network into the
    EXISTING compiled shard_map step (same shapes, zero retraces);
    only topology events rebuild it."""
    net, nbrs = _setup("fog")
    eng = core.ReplayEngine(net, phi0=core.spt_phi_sparse(net, nbrs),
                            driver="distributed")
    eng.iterate(2)
    step_before = eng.state.step
    rec = eng.apply_event(core.RateScale(1.25))
    assert rec.kind == "rate"
    assert eng.state.step is step_before           # no rebuild
    np.testing.assert_allclose(np.asarray(eng.net.r),
                               1.25 * np.asarray(net.r))
    eng.iterate(2)
    assert eng.state.costs[-1] <= eng.state.costs[0] * (1.0 + 1e-12)
    # a topology event DOES rebuild (the index tiles change)
    eng.apply_event(core.NodeFail(core.hub_node(net)))
    assert eng.state.step is not step_before
    eng.iterate(1)
    check_invariants(eng.net, eng.phi, eng.nbrs)


@pytest.mark.parametrize("driver", ["run", "distributed"])
def test_event_keeps_warm_sigma(driver):
    """An event's warm re-baseline starts the next segment from the
    previous segment's sigma (back at 1, the first steps after every
    event overshot and were rejected), unless that segment stopped on
    a sigma blow-up."""
    net, nbrs = _setup("abilene")
    eng = core.ReplayEngine(net, phi0=core.spt_phi_sparse(net, nbrs),
                            driver=driver)
    eng.state.sigma = 16.0
    eng.apply_event(core.RateScale(1.1))
    assert eng.state.sigma == 16.0
    eng.state.sigma = 4.0 ** 21                     # past the blow-up stop
    eng.apply_event(core.RateScale(0.9))
    assert eng.state.sigma == 1.0


# -------------------------------------------------------- property loop
@pytest.mark.parametrize("seed", [1, 7])
def test_randomized_schedule_invariants(seed):
    """After EVERY event of a randomized 20-event schedule the live
    iterate is feasible + loop-free and cost recovers monotonically
    within each segment (hypothesis-style seeded property loop)."""
    net, _ = _setup("abilene")
    sched = core.random_schedule(net, 20, seed=seed)
    assert sched.n_events == 20
    eng = core.ReplayEngine(net)
    checked = []

    def cb(rec, engine):
        check_invariants(engine.net, engine.phi, engine.nbrs)
        assert np.isfinite(rec.cost_after)
        checked.append(rec.kind)

    hist = eng.play(sched, tail_iters=3, callback=cb)
    assert len(checked) == 20
    # the schedule actually mixed event classes
    assert {"rate", "topology"} <= set(checked)
    # final iterate once more, independently of the callback
    check_invariants(eng.net, eng.phi, eng.nbrs)
    # monotone recovery inside every inter-event segment: the driver
    # only ever accepts downhill steps, shocks happen only AT events
    for rec in hist["records"]:
        seg = [rec.cost_after] + rec.segment_costs
        assert all(b <= a * (1.0 + 1e-12) for a, b in zip(seg, seg[1:])), \
            (rec.event, seg)
    assert np.isfinite(hist["final_cost"])


def test_warm_beats_cold_on_small_churn():
    """Across a failure→recovery roundtrip, the warm iterate needs
    measurably fewer iterations-to-target than cold SPT restarts
    (deterministic: seeded schedule, CPU floats).  A -1 (never reached
    target) folds to budget+1 via `iters_or_budget`, so a side that
    never converges correctly counts WORSE than one that barely does."""
    net, _ = _setup("fog")
    hub = core.hub_node(net)
    sched = core.ChurnSchedule((
        (3, core.NodeFail(hub)),
        (8, core.NodeRecover(hub)),
    ), name="mini")
    eng = core.ReplayEngine(net)
    hist = eng.play(sched, tail_iters=8, cold_baseline=True)
    repairs = [r for r in hist["records"] if r.warm_iters is not None]
    assert len(repairs) == 2
    warm = sum(core.iters_or_budget(r.warm_iters, r.segment_iters)
               for r in repairs)
    cold = sum(core.iters_or_budget(r.cold_iters, r.segment_iters)
               for r in repairs)
    assert warm < cold, (warm, cold)


def test_iters_to_target_sentinel():
    """-1 means 'never reached' — previously len(costs), which made a
    trajectory that never converged indistinguishable from one that
    converged on its very last step.  `iters_or_budget` folds the
    sentinel into budget+1: strictly worse than using the full budget."""
    assert core.iters_to_target([5.0, 4.0, 3.0], 3.5) == 2
    assert core.iters_to_target([5.0, 4.0, 3.0], 5.0) == 0
    assert core.iters_to_target([5.0, 4.0], 1.0) == -1
    assert core.iters_to_target([], 1.0) == -1
    assert core.iters_or_budget(2, 10) == 2
    assert core.iters_or_budget(0, 10) == 0
    assert core.iters_or_budget(-1, 10) == 11


def test_invariant_checks_switch(monkeypatch):
    """invariant_checks=True (the default) runs `check_invariants` on
    the repaired iterate after every event; False (the bench setting)
    runs none — the check is a host sync the streaming pipeline can't
    afford, so the switch must really remove it."""
    import repro.core.replay as replay_mod
    net, _ = _setup("abilene")
    calls = []
    real = replay_mod.check_invariants

    def counting(*a, **k):
        calls.append(1)
        return real(*a, **k)

    monkeypatch.setattr(replay_mod, "check_invariants", counting)
    eng = core.ReplayEngine(net)
    eng.iterate(2)
    eng.apply_event(core.RateScale(1.1))
    eng.apply_event(core.NodeFail(core.hub_node(net)))
    assert len(calls) == 2
    eng_off = core.ReplayEngine(net, invariant_checks=False)
    eng_off.iterate(2)
    eng_off.apply_event(core.RateScale(1.1))
    assert len(calls) == 2                         # unchanged


def test_dest_redraw_rebuilds_moved_task():
    """A destination re-draw force-rebuilds exactly the moved task from
    the new SPT (its surviving rows point at the OLD destination) and
    leaves the other tasks' routing untouched."""
    net, nbrs = _setup("abilene")
    eng = core.ReplayEngine(net)
    eng.iterate(6)
    before = np.asarray(eng.phi.result).copy()
    rec = eng.apply_event(core.DestRedraw(0, seed=123))
    assert rec.kind == "routing"
    new_dest = int(np.asarray(eng.net.dest)[0])
    assert new_dest != int(np.asarray(net.dest)[0])
    spt = np.asarray(core.gather_edges(core.spt_phi(eng.net).result,
                                       eng.nbrs))
    got = np.asarray(eng.phi.result)
    np.testing.assert_array_equal(got[0], spt[0])     # moved task: SPT
    # others: untouched up to the per-row renormalization
    np.testing.assert_allclose(got[1:], before[1:], atol=1e-6)
    check_invariants(eng.net, eng.phi, eng.nbrs)


# ---------------------------------------------------- recovery regression
def test_recovery_keeps_warm_iterate():
    """THE refeasibilize recovery-gap regression: fail a node, adapt,
    recover it — the repaired iterate must keep the adapted routing
    (only rows that LOST mass rebuild from the SPT, the recovered
    node's empty rows take their SPT rows), match the dense
    `refeasibilize` oracle bitwise, and stay loop-free.  Under the old
    any-empty-row-is-broken policy every task snapped back to the SPT
    tree, silently discarding the warm start."""
    net, nbrs = _setup("abilene")
    phi, _ = core.run(net, core.spt_phi(net), n_iters=10, method="sparse")
    sp = core.phi_to_sparse(phi, nbrs)

    hub = core.hub_node(net)
    net_f = core.fail_node(net, hub)
    sp_f, nbrs_f = core.refeasibilize_sparse(net_f, sp, nbrs)
    sp_f, _ = core.run(net_f, sp_f, n_iters=4, method="sparse")

    # recovery: pristine topology returns, iterate still on failed nbrs
    sp_r, nbrs_r = core.refeasibilize_sparse(net, sp_f, nbrs_f)

    # dense-oracle parity (conversion boundary = old nbrs).  The data
    # renormalization sums V+1 dense columns vs Dmax+1 slots, which XLA
    # may reduce in different orders, so data parity is to 1 ulp; the
    # result policy (who gets rebuilt) must agree exactly.
    want = core.refeasibilize(net, core.sparse_to_phi(sp_f, nbrs_f, net.V))
    got = core.sparse_to_phi(sp_r, nbrs_r, net.V)
    np.testing.assert_allclose(np.asarray(got.data),
                               np.asarray(want.data), atol=1e-6, rtol=0)
    np.testing.assert_array_equal(np.asarray(got.result),
                                  np.asarray(want.result))

    # THE regression: recovery only ADDS edges back (failed graph ⊂
    # pristine graph), so no row loses mass — the ONLY tasks that may
    # rebuild are those the recovered node immediately computes direct
    # input for (its result row must carry that flow; leaving it empty
    # would drop it from the objective); every other task keeps its
    # adapted routing, merely renormalized, with the recovered node's
    # empty row filled from the SPT (left empty, it would drop the
    # results later steps route into it).  Under the old policy every
    # task snapped to the SPT here.
    res_f = np.asarray(core.sparse_to_phi(sp_f, nbrs_f, net.V).result,
                       dtype=np.float64)
    rs = res_f.sum(-1, keepdims=True)
    spt_dense = np.asarray(core.spt_phi(net).result)
    renorm = np.where(rs > 1e-12, res_f / np.maximum(rs, 1e-30), spt_dense)
    local = np.asarray(sp_r.local[..., 0])
    is_dest = (np.arange(net.V)[None] == np.asarray(net.dest)[:, None])
    sourced = ((np.asarray(net.r) * local > 1e-12)
               & (rs[..., 0] <= 1e-12) & ~is_dest)
    must_rebuild = sourced.any(-1)                      # [S]
    assert not must_rebuild.all(), "no task should keep warm state?!"
    expected = np.where(must_rebuild[:, None, None], spt_dense, renorm)
    np.testing.assert_allclose(np.asarray(got.result), expected, atol=1e-6,
                               err_msg="recovery rebuilt undamaged rows")
    # the recovered hub routes the results of every task not destined
    # to it, where the old policy left its rows empty
    hub_rows = np.asarray(got.result)[:, hub].sum(-1)
    assert (hub_rows[~is_dest[:, hub]] > 0.5).all()
    spt_sp = np.asarray(core.gather_edges(core.spt_phi(net).result, nbrs_r))
    res_r = np.asarray(sp_r.result)
    reset = [np.array_equal(res_r[s], spt_sp[s]) for s in range(net.S)]
    assert not all(reset), "recovery wiped the whole warm iterate"

    check_invariants(net, sp_r, nbrs_r)
    # and the recovered system keeps descending from the warm point
    _, h = core.run(net, sp_r, n_iters=4, method="sparse")
    assert h["final_cost"] <= h["costs"][0] * (1.0 + 1e-12)


def test_source_redraw_onto_recovered_node_repairs_flow():
    """A source landing on a node whose result row is empty (it just
    recovered) must take the repair path — the no-repair "rate" path
    would leave that node's result flow silently dropped from the
    objective.  After ANY source re-draw the live iterate has no
    direct-source node with an empty result row."""
    net, _ = _setup("abilene")
    hub = core.hub_node(net)
    eng = core.ReplayEngine(net)
    eng.iterate(3)
    eng.apply_event(core.NodeFail(hub))
    eng.iterate(2)
    eng.apply_event(core.NodeRecover(hub))
    rec = eng.apply_event(core.SourceRedraw(0, seed=2))
    assert rec.kind == "routing"                  # repair path taken
    r = np.asarray(eng.net.r)
    assert r[0, hub] > 0.0                        # the hazard actually hit
    local = np.asarray(eng.phi.local[..., 0])
    rsum = np.asarray(eng.phi.result).sum(-1)     # padding is exactly 0
    is_dest = (np.arange(net.V)[None] == np.asarray(eng.net.dest)[:, None])
    dropped = (r * local > 1e-12) & (rsum <= 1e-12) & ~is_dest
    assert not dropped.any(), "direct-source rows with no result routing"
    check_invariants(eng.net, eng.phi, eng.nbrs)


def test_refeasibilize_leaves_noop_unchanged():
    """An identity 'topology change' (same graph) must not rebuild
    anything — rows map onto themselves and only renormalize."""
    net, nbrs = _setup("fog")
    phi, _ = core.run(net, core.spt_phi(net), n_iters=6, method="sparse")
    sp = core.phi_to_sparse(phi, nbrs)
    sp2, nbrs2 = core.refeasibilize_sparse(net, sp, nbrs)
    np.testing.assert_allclose(np.asarray(sp2.data), np.asarray(sp.data),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(sp2.result),
                               np.asarray(sp.result), atol=1e-6)


# ---------------------------------------------------- round-trip identity
def _line_net():
    """A 6-node instance whose repair outcomes are predictable in
    closed form: chain 0-1-2-3-4 plus node 5 hanging off BOTH 1 and 2,
    one task sourced at 0 with destination 4, unit linear costs.  The
    SPT routes node 5's (flow-free) result row via 2 — strictly fewer
    hops than via 1 — so edge (1,5) carries zero φ mass in EITHER
    direction and cutting it damages nothing."""
    import jax.numpy as jnp
    V, S = 6, 1
    adj = np.zeros((V, V), bool)
    for u, v in ((0, 1), (1, 2), (2, 3), (3, 4), (1, 5), (2, 5)):
        adj[u, v] = adj[v, u] = True
    d = np.where(adj, 1.0, 1.0)
    r = np.zeros((S, V))
    r[0, 0] = 1.0
    from repro.core.costs import Cost
    from repro.core.network import CECNetwork
    return CECNetwork(
        adj=jnp.asarray(adj),
        link_cost=Cost("linear", jnp.asarray(d)),
        comp_cost=Cost("linear", jnp.asarray(np.ones(V))),
        dest=jnp.asarray([4], dtype=jnp.int32),
        r=jnp.asarray(r),
        a=jnp.asarray([0.5]),
        w=jnp.asarray(np.ones((S, V))),
        task_type=jnp.asarray([0], dtype=jnp.int32))


def _assert_sparse_equal(a, b, msg=""):
    np.testing.assert_array_equal(np.asarray(a.data), np.asarray(b.data),
                                  msg)
    np.testing.assert_array_equal(np.asarray(a.local),
                                  np.asarray(b.local), msg)
    np.testing.assert_array_equal(np.asarray(a.result),
                                  np.asarray(b.result), msg)


def test_link_cut_restore_roundtrip_is_bitwise_identity():
    """Cut an edge that carries NO φ mass in either direction, restore
    it immediately (zero iterations in between): the double
    `refeasibilize_sparse` must be a bitwise identity on φ — rows remap
    onto themselves and the renormalizer divides by exactly 1.0."""
    net = _line_net()
    nbrs = core.build_neighbors(net.adj)
    phi0 = core.spt_phi_sparse(net, nbrs)
    # node 5 really does route via 2: its φ⁰ row is one-hot on 2's slot
    via = np.asarray(nbrs.out_nbr)[5][np.asarray(phi0.result)[0, 5] > 0]
    assert list(via) == [2]

    st = core.ChurnState(net)
    st.apply(core.LinkCut(1, 5))
    phi_c, nbrs_c = core.refeasibilize_sparse(st.network(), phi0, nbrs)
    check_invariants(st.network(), phi_c, nbrs_c)
    st.apply(core.LinkRestore(1, 5))
    net_r = st.network()
    phi_r, nbrs_r = core.refeasibilize_sparse(net_r, phi_c, nbrs_c)

    np.testing.assert_array_equal(np.asarray(net_r.adj),
                                  np.asarray(net.adj))
    np.testing.assert_array_equal(np.asarray(nbrs_r.out_nbr),
                                  np.asarray(nbrs.out_nbr))
    _assert_sparse_equal(phi_r, phi0, "cut+restore must be identity")
    check_invariants(net, phi_r, nbrs_r)


def test_node_fail_recover_roundtrip_zeroes_only_failed_row():
    """Fail node 5 (it loses every exit), recover it immediately: every
    OTHER row round-trips bitwise to φ⁰, and node 5's result row comes
    back as its SPT row — it is flow-free (r[0,5]=0), so the task is
    not rebuilt, but an empty row would drop the results later steps
    route into node 5.  φ⁰ is the SPT, so the whole round trip is a
    bitwise identity."""
    net = _line_net()
    nbrs = core.build_neighbors(net.adj)
    phi0 = core.spt_phi_sparse(net, nbrs)

    st = core.ChurnState(net)
    st.apply(core.NodeFail(5))
    phi_f, nbrs_f = core.refeasibilize_sparse(st.network(), phi0, nbrs)
    check_invariants(st.network(), phi_f, nbrs_f)
    st.apply(core.NodeRecover(5))
    net_r = st.network()
    phi_r, nbrs_r = core.refeasibilize_sparse(net_r, phi_f, nbrs_f)

    np.testing.assert_array_equal(np.asarray(nbrs_r.out_nbr),
                                  np.asarray(nbrs.out_nbr))
    # node 5's row was emptied by the failure and refilled from the SPT
    assert np.asarray(phi_f.result)[0, 5].sum() == 0.0
    want_result = np.asarray(phi0.result)
    np.testing.assert_array_equal(np.asarray(phi_r.data),
                                  np.asarray(phi0.data))
    np.testing.assert_array_equal(np.asarray(phi_r.local),
                                  np.asarray(phi0.local))
    np.testing.assert_array_equal(np.asarray(phi_r.result), want_result)
    check_invariants(net, phi_r, nbrs_r)
    # and the round-tripped iterate still descends
    _, h = core.run(net, phi_r, n_iters=4, method="sparse")
    assert h["final_cost"] <= h["costs"][0] * (1.0 + 1e-12)


# ------------------------------------------------------- GEANT delivery
def _queue_cost(F, cap):
    """M/M/1 delay F/(cap - F), continued above 0.95 cap as its
    second-order expansion (the queue family the scenarios use)."""
    Fs = 0.95 * cap
    inner = np.minimum(F, Fs)
    dF = F - Fs
    ext = (Fs / (cap - Fs) + cap / (cap - Fs) ** 2 * dF
           + cap / (cap - Fs) ** 3 * dF ** 2)
    return np.where(F <= Fs, inner / (cap - inner), ext)


def _numpy_flows(net, phi, nbrs):
    """(largest undelivered share of a task's results, cost, loop-free)
    of the edge-slot φ on `net`, by plain numpy: the data and result
    flow equations t = inject + Φᵀ t solved with `numpy.linalg.solve`,
    one task at a time, on dense [V, V] routing matrices.  A support
    with a loop reads (inf, nan, False): its equations have no solution."""
    adj = np.asarray(net.adj)
    V = adj.shape[0]
    nbr = np.asarray(nbrs.out_nbr)
    mask = np.asarray(nbrs.out_mask)
    data = np.asarray(phi.data, np.float64) * mask
    res = np.asarray(phi.result, np.float64) * mask
    local = np.asarray(phi.local, np.float64)[..., 0]
    r = np.asarray(net.r, np.float64)
    a = np.asarray(net.a, np.float64)
    w = np.asarray(net.w, np.float64)
    dest = np.asarray(net.dest)
    rows = np.repeat(np.arange(V)[:, None], nbr.shape[1], axis=1)
    eye = np.eye(V)
    loss = 0.0
    F, G = np.zeros((V, V)), np.zeros(V)
    for s in range(data.shape[0]):
        Pd, Pr = np.zeros((V, V)), np.zeros((V, V))
        np.add.at(Pd, (rows, nbr), data[s])
        np.add.at(Pr, (rows, nbr), res[s])
        for P in (Pd, Pr):        # a loop-free support is nilpotent
            sup = (P > 0).astype(np.float64)
            if np.linalg.matrix_power(sup, V).any():
                return np.inf, np.nan, False
        t = np.linalg.solve(eye - Pd.T, r[s])
        g = t * local[s]
        t_res = np.linalg.solve(eye - Pr.T, a[s] * g)
        made = a[s] * g.sum()
        if made > 0.0:
            loss = max(loss, abs(made - t_res[dest[s]]) / made)
        F += t[:, None] * Pd + t_res[:, None] * Pr
        G += w[s] * g
    cap = np.asarray(net.link_cost.params, np.float64)
    comp = np.asarray(net.comp_cost.params, np.float64)
    cost = (np.sum(np.where(adj, _queue_cost(F, cap), 0.0))
            + np.sum(_queue_cost(G, comp)))
    return loss, float(cost), True


def test_geant_churn_delivers_every_result():
    """The first 27 events of a seeded GEANT churn stream (three node
    failures and recoveries, link cuts of the one degree-4 node, rate
    and routing events), through `apply_event` + `iterate` as an
    operator drives it: after EVERY event each task's results all reach
    its destination, the reported cost is the plain numpy cost of the
    live φ, and both supports are loop-free.  Before the empty-row fill
    of `refeasibilize_sparse`, a recovered node's empty result row
    dropped up to 87 % of a task's results on this stream, while the
    reported cost, which leaves the dropped results out too, still
    matched."""
    net = core.make_scenario(core.TABLE_II["geant"])
    events = core.random_schedule(net, 60, seed=1).events[:27]
    kinds = [type(ev) for _, ev in events]
    assert kinds.count(core.NodeFail) == kinds.count(core.NodeRecover) == 3
    eng = core.ReplayEngine(net, invariant_checks=False)
    eng.iterate(20)
    for i, (_, ev) in enumerate(events):
        eng.apply_event(ev)
        eng.iterate(1 + i % 3)
        loss, cost, loop_free = _numpy_flows(eng.net, eng.phi, eng.nbrs)
        assert loop_free, (i, ev)
        assert loss <= 1e-5, (i, ev, loss)
        np.testing.assert_allclose(eng.cost, cost, rtol=1e-5,
                                   err_msg=f"event {i}: {ev}")


# ----------------------------------------------------------------- scale
@pytest.mark.slow
def test_sw1000_churn_replay():
    """The canned multi-event sw_1000 schedule end-to-end: ≥5 mixed
    rate/failure/recovery events, every post-event iterate feasible and
    loop-free (task slice), warm-start needing no more
    iterations-to-target than cold SPT restarts."""
    net, _ = _setup("sw_1000")
    sched = core.churn_schedule("sw_1000_churn", net)
    assert sched.n_events >= 5
    kinds = {core.event_kind(e) for _, e in sched.events}
    assert {"rate", "topology"} <= kinds

    eng = core.ReplayEngine(net)

    def cb(rec, engine):
        check_invariants(engine.net, engine.phi, engine.nbrs,
                         n_loop_tasks=4)

    hist = eng.play(sched, tail_iters=5, cold_baseline=True, callback=cb)
    assert len(hist["records"]) == sched.n_events
    check_invariants(eng.net, eng.phi, eng.nbrs, n_loop_tasks=4)
    repairs = [r for r in hist["records"] if r.warm_iters is not None]
    assert repairs, "no repair events measured"
    warm = sum(core.iters_or_budget(r.warm_iters, r.segment_iters)
               for r in repairs)
    cold = sum(core.iters_or_budget(r.cold_iters, r.segment_iters)
               for r in repairs)
    assert warm <= cold, (warm, cold)
    assert np.isfinite(hist["final_cost"])
