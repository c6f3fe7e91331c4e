"""Faults planted under the timed path, each breaking what the window
drives, so that the comparison can be seen to catch it:

  unchanged     every SGP step returns its state unchanged
  half_dropped  half of the tasks left out, the cost scaled up from the
                rest
  half_frozen   every SGP step updates the first half of the tasks only;
                the rest stay at the shortest-path start, and the cost
                of the whole is reported as it is
  altered       an answer altered where it is produced: the result rows
                of the final φ emptied (empty rows pass the simplex rule;
                results computed away from their destination go
                undelivered)

`plant(name, setter)` installs one: tests pass `monkeypatch.setattr`,
`bench/control.py` plain `setattr`.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from repro import core
from repro.core import sgp as sgp_mod
from repro.core.network import cost_of_carry


def _unchanged(step):
    def broken(net, phi, fl, consts, **kw):
        return phi, fl, cost_of_carry(net, fl, kw.get("nbrs"))
    return broken


def _half_frozen(step):
    def broken(net, phi, fl, consts, **kw):
        kw["active"] = jnp.arange(net.S) < net.S // 2
        return step(net, phi, fl, consts, **kw)
    return broken


def _half_dropped(run):
    def broken(net, phi0, **kw):
        keep = (jnp.arange(net.S) < net.S // 2).astype(net.r.dtype)
        phi, hist = run(dataclasses.replace(net, r=net.r * keep[:, None]),
                        phi0, **kw)
        return phi, dict(hist, final_cost=2.0 * hist["final_cost"])
    return broken


def _altered(run):
    def broken(net, phi0, **kw):
        phi, hist = run(net, phi0, **kw)
        return dataclasses.replace(
            phi, result=jnp.zeros_like(phi.result)), hist
    return broken


FAULTS = {
    "unchanged": (sgp_mod, "sgp_step_flows", _unchanged),
    "half_dropped": (core, "run", _half_dropped),
    "half_frozen": (sgp_mod, "sgp_step_flows", _half_frozen),
    "altered": (core, "run", _altered),
}


def plant(name: str, setter=setattr) -> None:
    mod, attr, wrap = FAULTS[name]
    setter(mod, attr, wrap(getattr(mod, attr)))
