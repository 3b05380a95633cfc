"""Differential battery for the fused FISTA z_L kernel (`ops.fista_zlast`)
and the pad-to-tile dispatch that feeds it.

The kernel unrolls the FISTA loop into one Pallas dispatch per iteration
with host-precomputed momentum scalars; `update_z_last_reference` keeps the
pre-kernel fori_loop as ground truth. Equivalence runs in f64 interpret mode
— the kernel computes in the operand dtype (promoted to at least f32), so at
f64 the two iteration maps agree to ~1e-12 on every ragged real-dataset
shape (real-graph node counts 2485, 2708, 3327) without any tile alignment.

The battery also pins the dispatch structure itself: a trace-level jaxpr
test counts exactly one pallas_call per FISTA iteration (plus the initial
gradient step), and the seeded end-to-end golden test locks the `ref` and
`interpret` dispatch families to one recorded objective trajectory.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from repro.analysis.jaxpr_tools import count_primitive

from repro.core import pdadmm, subproblems as sp
from repro.core.pdadmm import ADMMConfig
from repro.graph.datasets import synthetic
from repro.kernels import ops
from repro.kernels.fista_zlast import momentum_schedule, prox_carry_weight


@pytest.fixture
def x64():
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", False)


def _problem(V, C, seed=0, mask="some", dtype=jnp.float64):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    a = jax.random.normal(ks[0], (V, C), dtype)
    z0 = jax.random.normal(ks[1], (V, C), dtype)
    labels = jax.random.randint(ks[2], (V,), 0, C)
    if mask == "all":
        m = jnp.ones((V,), dtype)
    elif mask == "none":
        m = jnp.zeros((V,), dtype)
    else:
        m = (jax.random.uniform(ks[3], (V,)) > 0.4).astype(dtype)
    return a, z0, labels, m


def _assert_kernel_matches_reference(a, z0, labels, m, nu, n_iters,
                                     atol=1e-10):
    want = sp.update_z_last_reference(a, z0, labels, m, nu, n_iters)
    got = ops.fista_zlast(a, z0, labels, m, nu=nu, n_iters=n_iters,
                          interpret=True)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=atol)


# --- ragged-shape sweep (the real-dataset sizes that used to fall to ref) ---

@pytest.mark.slow           # policy-independent (explicit interpret=True)
@pytest.mark.parametrize("V", [1, 7, 2485, 2708, 3327])
@pytest.mark.parametrize("C", [3, 6, 7, 40])
def test_fista_zlast_ragged_shapes(x64, V, C):
    a, z0, labels, m = _problem(V, C, seed=V * 41 + C)
    _assert_kernel_matches_reference(a, z0, labels, m, nu=0.5, n_iters=8)


@pytest.mark.parametrize("mask", ["all", "none"])
@pytest.mark.parametrize("V,C", [(7, 3), (97, 6), (2485, 7)])
def test_fista_zlast_mask_extremes(x64, mask, V, C):
    """All-labeled (pure CE+prox) and fully-unlabeled (prox-only flow —
    z converges toward a) both match the reference."""
    a, z0, labels, m = _problem(V, C, seed=5, mask=mask)
    _assert_kernel_matches_reference(a, z0, labels, m, nu=0.5, n_iters=10)
    if mask == "none":
        got = ops.fista_zlast(a, z0, labels, m, nu=1.0, n_iters=60,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(a), atol=1e-3)


@pytest.mark.parametrize("nu", [1e-6, 1e-2, 1.0, 1e4])
def test_fista_zlast_nu_extremes(x64, nu):
    """ν spans prox-negligible (pure CE descent) to prox-dominated
    (step = 1/(1+ν) → 0, z barely moves)."""
    a, z0, labels, m = _problem(193, 7, seed=9)
    _assert_kernel_matches_reference(a, z0, labels, m, nu=nu, n_iters=12)


@pytest.mark.parametrize("n_iters", [0, 1, 2, 15, 40])
def test_fista_zlast_iteration_counts(x64, n_iters):
    """The unrolled dispatch chain tracks the fori_loop at every depth,
    including the 0-iteration edge (just the initial gradient step)."""
    a, z0, labels, m = _problem(61, 6, seed=3)
    _assert_kernel_matches_reference(a, z0, labels, m, nu=0.3,
                                     n_iters=n_iters)


def test_fista_zlast_head_folded_columns(x64):
    """n_classes < width (the distributed head-folded layout): CE on the
    first C columns, prox-only flow on the rest — matches the shared jnp
    oracle and, on the logit block, the reference run on the slice."""
    V, h, C = 131, 64, 5
    a, z0, labels, m = _problem(V, h, seed=7)
    labels = jnp.minimum(labels, C - 1)
    got = ops.fista_zlast(a, z0, labels, m, nu=0.5, n_iters=9, n_classes=C,
                          interpret=True)
    want = sp.fista_ce(a, z0, labels, m, 0.5, 9, n_classes=C)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-10)
    # columns >= C never feed the softmax: they must equal the pure-prox flow
    prox = sp.fista_ce(a, z0, labels, jnp.zeros_like(m), 0.5, 9)
    np.testing.assert_allclose(np.asarray(got[:, C:]), np.asarray(prox[:, C:]),
                               atol=1e-10)


@pytest.mark.parametrize("path", ["kernel", "jnp"])
@pytest.mark.parametrize("V,C,width,n_iters,nu", [
    (300, 1, 1, 15, 1e-2),        # C == width: the unsplit path
    (300, 1, 200, 15, 1e-2),
    (513, 3, 1000, 15, 1e-2),     # the head-folded pubmed layer's shape
    (513, 3, 3, 1, 1.0),
    (300, 3, 200, 0, 1e-3),
    (513, 47, 200, 1, 1e-3),
    (300, 47, 1000, 15, 1.0),
    (513, 128, 128, 15, 1e-3),
    (300, 128, 200, 15, 1e-2),
    (513, 130, 1000, 0, 1e-2),
    (300, 130, 200, 15, 1.0),
    (513, 130, 130, 1, 1e-2),
])
def test_update_z_last_column_split(x64, monkeypatch, path, V, C, width,
                                    n_iters, nu):
    """`update_z_last(..., n_classes=C)` runs FISTA on the C logit columns
    and writes the rest as α·z_old + (1 − α)·a: the same result as the
    full-width solve, on the kernel and the jnp path. α is the weight of
    z_old after iterating the scalar prox-only FISTA recurrence."""
    a, z0, labels, m = _problem(V, width, seed=V + 7 * C + width)
    labels = jnp.minimum(labels, C - 1)
    full = ops.fista_zlast(a, z0, labels, m, nu=nu, n_iters=n_iters,
                           n_classes=C, interpret=True)
    np.testing.assert_allclose(
        np.asarray(full),
        np.asarray(ops.fista_zlast(a, z0, labels, m, nu=nu, n_iters=n_iters,
                                   n_classes=C, use_pallas=False)),
        atol=1e-10)
    monkeypatch.setenv("REPRO_KERNELS", "interpret")
    got = sp.update_z_last(a, z0, labels, m, nu, n_iters, n_classes=C,
                           use_kernels=path == "kernel")
    assert got.shape == full.shape and got.dtype == full.dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(full), atol=1e-10)

    scalar = sp.fista_prox(lambda z: nu * z, jnp.ones((), jnp.float64),
                           1.0 / (1.0 + nu), n_iters)   # a = 0, z_old = 1
    assert prox_carry_weight(n_iters, nu) == pytest.approx(float(scalar),
                                                           abs=1e-14)


def test_block_admm_ce_path_matches_generic_risk(x64):
    """`block_admm.make_block_iterate`'s two z-last routes — the generic
    `fista_prox` on jax.grad(risk_fn) and the `labels=`-driven
    `ops.fista_zlast` dispatch — compute the same iteration when the risk
    IS the masked CE."""
    from repro.core import block_admm as BA

    L, B, S, d = 3, 2, 4, 8
    block_fn = lambda W, p: jnp.tanh(p @ W)
    Ws = jax.random.normal(jax.random.PRNGKey(0), (L, d, d),
                           jnp.float64) * 0.3
    x0 = jax.random.normal(jax.random.PRNGKey(1), (B, S, d), jnp.float64)
    labels = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, d)
    mask = jnp.ones((B, S), jnp.float64)

    def risk_fn(z):
        zf, lf = z.reshape(-1, d), labels.reshape(-1)
        logp = jax.nn.log_softmax(zf, axis=-1)
        nll = -jnp.take_along_axis(logp, lf[:, None], axis=-1)[:, 0]
        return jnp.sum(nll * mask.reshape(-1))

    cfg = ADMMConfig(nu=1e-2, rho=1.0)
    st = BA.init_block_state(block_fn, Ws, x0, L, cfg)
    it_gen = BA.make_block_iterate(block_fn, risk_fn, cfg)
    it_ce = BA.make_block_iterate(block_fn, risk_fn, cfg, labels=labels,
                                  label_mask=mask)
    s_gen, m_gen = it_gen(st, x0)
    s_ce, m_ce = it_ce(st, x0)
    np.testing.assert_allclose(np.asarray(s_ce.z), np.asarray(s_gen.z),
                               atol=1e-10)
    np.testing.assert_allclose(float(m_ce["objective"]),
                               float(m_gen["objective"]), rtol=1e-10)


def test_update_z_last_dispatch_equals_reference_on_ref_policy(monkeypatch):
    """`subproblems.update_z_last` (the rewired call-site entry point)
    reproduces the reference bit-for-bit on the jnp dispatch path."""
    monkeypatch.setenv("REPRO_KERNELS", "ref")
    a, z0, labels, m = _problem(57, 6, seed=2, dtype=jnp.float32)
    got = sp.update_z_last(a, z0, labels, m, 0.5, 11)
    want = sp.update_z_last_reference(a, z0, labels, m, 0.5, 11)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


# --- trace-level dispatch structure -----------------------------------------

@pytest.mark.parametrize("n_iters", [1, 7, 15])
def test_one_kernel_dispatch_per_fista_iteration(n_iters):
    """The fused solve lowers to EXACTLY n_iters + 1 pallas_calls (one per
    FISTA iteration plus the initial gradient step) — the per-iteration
    softmax/CE-grad/momentum dispatch chain is gone from the trace."""
    a = jnp.zeros((96, 8))
    labels = jnp.zeros((96,), jnp.int32)
    m = jnp.ones((96,))
    jaxpr = jax.make_jaxpr(
        lambda a_, z_, l_, m_: ops.fista_zlast(
            a_, z_, l_, m_, nu=0.5, n_iters=n_iters, interpret=True))(
        a, a, labels, m)
    assert count_primitive(jaxpr.jaxpr, "pallas_call") == n_iters + 1


def test_momentum_schedule_matches_fori_loop_t_sequence():
    """Host-side momentum scalars == the reference's carried t recursion."""
    ms = momentum_schedule(6)
    assert ms[0] == 0.0 and ms[1] == 0.0      # t_1 = 1 -> first mom is 0 too
    t = 1.0
    for k in range(6):
        t_new = (1.0 + np.sqrt(1.0 + 4.0 * t * t)) / 2.0
        assert ms[k + 1] == pytest.approx((t - 1.0) / t_new, abs=1e-15)
        t = t_new
    assert len(ms) == 7


# --- seeded end-to-end convergence golden -----------------------------------

GOLDEN_CITESEER = {
    # recorded from the seeded run below (REPRO_KERNELS=ref, jax 0.9.0 CPU,
    # whose default jax_threefry_partitionable=True draws a different init
    # than 0.4.x did); both dispatch families must land on this trajectory
    "final_objective": 4.4958619e-3,
    "rtol": 2e-3,
}


def _train_citeseer(policy: str, monkeypatch, epochs: int = 30):
    monkeypatch.setenv("REPRO_KERNELS", policy)
    ds = synthetic("citeseer", seed=0, scale=0.03)
    X = ds.augmented(2)
    dims = [X.shape[1], 32, 32, ds.n_classes]
    cfg = ADMMConfig(nu=1e-2, rho=1.0)
    _, hist = pdadmm.train(jax.random.PRNGKey(0), X, ds.labels, ds.masks,
                           dims, cfg, epochs=epochs)
    return hist


@pytest.mark.slow           # runs BOTH policies itself via monkeypatch
def test_e2e_citeseer_golden_ref_vs_interpret(monkeypatch):
    """30 seeded iterations on the synthetic citeseer config under BOTH
    dispatch families: objective monotone-trending, final value pinned to
    the recorded golden, ref and interpret trajectories in lockstep."""
    h_ref = _train_citeseer("ref", monkeypatch)
    h_int = _train_citeseer("interpret", monkeypatch)
    for name, hist in (("ref", h_ref), ("interpret", h_int)):
        obj = hist["objective"]
        assert len(obj) == 30
        viol = sum(1 for x, y in zip(obj, obj[1:]) if y > x + 1e-5 * abs(x))
        assert viol == 0, f"{name}: {viol} objective increases"
        assert obj[-1] < obj[0]
        np.testing.assert_allclose(obj[-1], GOLDEN_CITESEER["final_objective"],
                                   rtol=GOLDEN_CITESEER["rtol"],
                                   err_msg=f"{name} family drifted off golden")
    np.testing.assert_allclose(h_ref["objective"], h_int["objective"],
                               rtol=1e-3)
