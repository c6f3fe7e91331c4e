import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# Tier-1 guard: the suite must behave identically on CPU-only CI and on
# accelerator hosts.  Pin the CPU backend before jax initializes (a
# stray TPU/GPU would silently switch every kernel dispatch to the
# compiled Pallas path and change tolerances); set JAX_PLATFORMS
# explicitly in the environment to override.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402  (after the platform pin, by design)
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _interpret_kernels_off_tpu(monkeypatch):
    """Off-TPU, remap impl="pallas" kernel dispatch to the Pallas
    interpreter so kernel tests exercise the kernel bodies instead of
    failing/skipping on CPU-only CI (impl=None still resolves to the
    jnp reference, exactly as in production)."""
    if jax.default_backend() == "tpu":
        yield
        return
    from repro.kernels import ops
    real_pick = ops._pick
    monkeypatch.setattr(
        ops, "_pick",
        lambda impl, op: ("pallas_interpret"
                          if real_pick(impl, op) == "pallas"
                          else real_pick(impl, op)))
    yield
