"""Per-kernel allclose validation: Pallas (interpret=True on CPU) vs the
pure-jnp oracles in repro.kernels.ref, swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(7)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,KV,S,hd,causal", [
    (1, 4, 2, 256, 64, True),
    (2, 8, 8, 128, 128, False),
    (1, 4, 1, 512, 64, True),
    (1, 2, 2, 384, 128, True),
])
def test_flash_attention(B, H, KV, S, hd, causal, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, H, S, hd), dtype)
    k = jax.random.normal(ks[1], (B, KV, S, hd), dtype)
    v = jax.random.normal(ks[2], (B, KV, S, hd), dtype)
    out = ops.flash_attention(q, k, v, causal=causal,
                              impl="pallas_interpret")
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,KV,G,S,hd", [
    (2, 2, 4, 1024, 64),
    (1, 8, 1, 512, 128),
    (3, 4, 2, 2048, 64),
])
def test_decode_attention(B, KV, G, S, hd, dtype):
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, KV, G, hd), dtype)
    k = jax.random.normal(ks[1], (B, KV, S, hd), dtype)
    v = jax.random.normal(ks[2], (B, KV, S, hd), dtype)
    lengths = jax.random.randint(ks[3], (B,), 1, S + 1)
    out = ops.decode_attention(q, k, v, lengths, impl="pallas_interpret")
    want = ref.decode_attention_ref(
        q.reshape(B, KV * G, hd), jnp.swapaxes(k, 1, 2),
        jnp.swapaxes(v, 1, 2), lengths).reshape(B, KV, G, hd)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32])
@pytest.mark.parametrize("B,L,H,P,N,chunk,bh", [
    (2, 64, 8, 16, 32, 16, 4),
    (1, 128, 4, 64, 128, 32, 4),
    (2, 256, 16, 32, 16, 64, 8),
])
def test_ssd_scan(B, L, H, P, N, chunk, bh, dtype):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, L, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, H))).astype(dtype)
    A = -jnp.exp(0.5 * jax.random.normal(ks[2], (H,)))
    Bm = jax.random.normal(ks[3], (B, L, N), dtype)
    Cm = jax.random.normal(ks[4], (B, L, N), dtype)
    out = ops.ssd_scan(x, dt, A, Bm, Cm, impl="pallas_interpret",
                       chunk=chunk, block_h=bh)
    want, _ = ref.ssd_scan_ref(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        rtol=5e-3, atol=5e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("E,C,D,F", [
    (4, 128, 256, 128),
    (2, 256, 128, 384),
    (8, 128, 512, 256),
])
def test_moe_gmm(E, C, D, F, dtype):
    ks = jax.random.split(KEY, 2)
    x = jax.random.normal(ks[0], (E, C, D), dtype)
    w = jax.random.normal(ks[1], (E, D, F), dtype)
    out = ops.moe_gmm(x, w, impl="pallas_interpret")
    want = ref.moe_gmm_ref(x, w)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        **_tol(dtype))


def _rk(R, K, case=None):
    return pytest.param(R, K, case,
                        id="-".join(str(a) for a in (R, K, case) if a))


@pytest.mark.parametrize("R,K,case", [
    _rk(17, 8), _rk(100, 24), _rk(64, 112),
    # the solve cells' data and result rows: geant (S·V = 880, Dmax 4)
    # and sw_queue (12000, Dmax 9); neither R is a multiple of 128
    _rk(880, 5), _rk(880, 4), _rk(12000, 10), _rk(12000, 9),
    _rk(1000, 10, "blocked_rows"), _rk(300, 7, "M_floor"),
])
def test_simplex_project(R, K, case):
    ks = jax.random.split(KEY, 4)
    phi = jax.nn.softmax(jax.random.normal(ks[0], (R, K)), -1)
    delta = jax.nn.softplus(jax.random.normal(ks[1], (R, K)))
    M = jax.nn.softplus(jax.random.normal(ks[2], (R, K)))
    perm = jax.random.bernoulli(ks[3], 0.7, (R, K))
    perm = perm.at[:, 0].set(True)
    live = np.ones(R, bool)
    if case == "blocked_rows":          # every third row fully blocked
        live[::3] = False
        perm = perm & jnp.asarray(live)[:, None]
    if case == "M_floor":               # scalings at and near the 1e-12 floor
        M = M * 1e-12
    out = ops.simplex_project(phi, delta, M, perm, impl="pallas_interpret")
    want = ref.simplex_project_ref(phi, delta, M, perm)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out.sum(-1))[live], 1.0,
                               atol=1e-4)
    np.testing.assert_array_equal(np.asarray(out)[~live], 0.0)


def test_kernel_sgp_step_equivalence():
    """The Pallas QP kernel is a drop-in for the core projection: one
    SGP row batch projected via kernel == via the jnp path."""
    from repro import core
    net = core.make_scenario(core.TABLE_II["abilene"])
    phi = core.spt_phi(net)
    fl = core.compute_flows(net, phi)
    mg = core.compute_marginals(net, phi, fl)
    from repro.core.sgp import blocked_sets
    perm_d, _ = blocked_sets(net, phi, mg)
    S, V = net.S, net.V
    rows = phi.data.reshape(S * V, V + 1)
    delta = mg.delta_data.reshape(S * V, V + 1)
    M = jnp.ones_like(rows)
    perm = perm_d.reshape(S * V, V + 1)
    out = ops.simplex_project(rows, delta, M, perm,
                              impl="pallas_interpret")
    want = ref.simplex_project_ref(rows, delta, M, perm)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
