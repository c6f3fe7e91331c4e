"""Faults planted under the churn loop's timed path
(`bench/loops/churn.py`), each breaking what live churn drives, so that
the comparison can be seen to catch it; `harness/faults.py` holds the
faults every loop shares.

  old_recovery  a recovered node's result rows left empty (the repair
                policy before the shortest-path fill): results that
                later steps route into the node are never delivered
  ignored       every rate event dropped: the solver keeps the previous
                network while the deployment's load has moved

and the shared faults of `harness/faults.py`, planted on the warm path
alone (`ReplayEngine.iterate`'s chunks and events), so that the cold
solve the window starts from stays sound:

  warm_unchanged     every warm chunk returns its state unchanged
  warm_half_frozen   every warm chunk updates the first half of the
                     tasks only
  warm_half_dropped  every event's network leaves the second half of
                     the tasks out, and the cost is reported scaled up
                     from the rest

`plant(name, setter)` installs one: tests pass `monkeypatch.setattr`,
scripts plain `setattr`.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro import core
from repro.core import replay


def _old_recovery(repair):
    def broken(net, phi, nbrs, **kw):
        out, new_nbrs = repair(net, phi, nbrs, **kw)
        was_down = ~np.asarray(nbrs.out_mask).any(-1)
        back = was_down & np.asarray(new_nbrs.out_mask).any(-1)   # [V]
        sourced = np.asarray(net.r) * np.asarray(out.local[..., 0]) > 0
        empty = jnp.asarray(back[None] & ~sourced)                # [S, V]
        return dataclasses.replace(
            out, result=jnp.where(empty[..., None], 0.0, out.result)
        ), new_nbrs
    return broken


def _ignored(apply_event):
    def broken(self, event):
        if isinstance(event, core.RateScale):
            return None
        return apply_event(self, event)
    return broken


def _warm_unchanged(run_chunk):
    def broken(net, state, n_iters, **kw):
        return state
    return broken


def _warm_half_frozen(run_chunk):
    def broken(net, state, n_iters, **kw):
        state.active = jnp.arange(net.S) < net.S // 2
        return run_chunk(net, state, n_iters, **kw)
    return broken


def _half_rates(apply_event):
    def broken(self, event):
        rec = apply_event(self, event)
        keep = (jnp.arange(self.net.S) < self.net.S // 2).astype(
            self.net.r.dtype)
        sigma = self.state.sigma
        self.net = dataclasses.replace(self.net,
                                       r=self.net.r * keep[:, None])
        self._init_state(self.phi)
        self.state.sigma = sigma
        return rec
    return broken


def _doubled_cost(cost):
    return property(lambda self: 2.0 * cost.fget(self))


FAULTS = {
    "old_recovery": [(replay, "refeasibilize_sparse", _old_recovery)],
    "ignored": [(replay.ReplayEngine, "apply_event", _ignored)],
    "warm_unchanged": [(replay, "run_chunk", _warm_unchanged)],
    "warm_half_frozen": [(replay, "run_chunk", _warm_half_frozen)],
    "warm_half_dropped": [(replay.ReplayEngine, "apply_event", _half_rates),
                          (replay.ReplayEngine, "cost", _doubled_cost)],
}


def plant(name: str, setter=setattr) -> None:
    for obj, attr, wrap in FAULTS[name]:
        setter(obj, attr, wrap(getattr(obj, attr)))
