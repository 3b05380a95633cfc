"""pdADMM-G / pdADMM-G-Q: the paper's Algorithm 1, single-host reference.

All six variable families update *in parallel across layers* — each layer's
update reads only previous-iteration values of its neighbors (that is what
makes the algorithm model-parallel; the distributed runtime in
``parallel/stage_parallel.py`` runs the same math with layers sharded over
mesh stages and neighbor exchange on ICI).

Variable layout (0-based, node-major):
  p[l] : [V, dims[l]]     layer input,  l = 0..L-1, p[0] = X (never updated)
  W[l] : [dims[l], dims[l+1]]
  b[l] : [dims[l+1]]
  z[l] : [V, dims[l+1]]
  q[l] : [V, dims[l+1]]   layer output, l = 0..L-2
  u[l] : [V, dims[l+1]]   dual,         l = 0..L-2
  constraint: p[l+1] = q[l]

Fast path (the default ``iterate``): each layer's residual r = z - pW - b is
computed ONCE per iteration (``kernels.ops.fused_linear(mode="residual")``)
and chained through the whole family — the p-update returns the residual at
the new p, the W-update consumes and re-returns it, the exact b-solve and
the z-update's pre-activation then cost zero matmuls:

    b⁺ = b + mean(r, axis=0)          (mean over nodes of the residual)
    a  = pW + b⁺ = z - (r - mean(r))

Backtracking never re-evaluates φ on tensors (``subproblems`` incremental
engines), so one layer costs 5 matmul-shaped contractions total: the entry
residual, r Wᵀ and gW in the p-update, pᵀr and pg in the W-update. When the
hidden block is equal-width (the paper's large-scale setup), those five run
layer-STACKED (``jax.vmap`` over an [L_h, ...] block, mirroring
``stage_parallel.StackState``), collapsing O(6L) kernel dispatches per
iteration to O(1) per variable family. The last-layer FISTA solve rides the
fused ``ops.fista_zlast`` dispatch (one kernel per FISTA iteration).
``iterate_reference`` keeps the pre-optimization math as the ground-truth
oracle.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core import subproblems as sp
from repro.core.quantize import QuantGrid, uniform_grid


class ADMMState(NamedTuple):
    p: List[jax.Array]
    W: List[jax.Array]
    b: List[jax.Array]
    z: List[jax.Array]
    q: List[jax.Array]
    u: List[jax.Array]
    tau: List[jax.Array]    # last accepted τ_l  (warm-started each iter)
    theta: List[jax.Array]  # last accepted θ_l


@dataclasses.dataclass(frozen=True)
class ADMMConfig:
    nu: float = 1e-2
    rho: float = 1.0
    fista_iters: int = 15
    tau0: float = 1.0
    backtrack_decay: float = 0.5   # warm start: next τ0 = τ_used * decay
    quantize_p: bool = False
    quantize_q: bool = False
    grid: Optional[QuantGrid] = None
    # fast-path knobs (numerics are identical up to float rounding):
    use_kernels: bool = True       # heavy ops through kernels.ops dispatch
    stack_hidden: bool = True      # layer-stacked vmap over equal-width block


def relu(x):
    return jnp.maximum(x, 0.0)


def init_state(key, X, dims: Sequence[int], config: ADMMConfig) -> ADMMState:
    """dims: [n_0, n_1, ..., n_L] (n_0 = K*d input width, n_L = #classes).
    Initialization follows the paper's code: forward-propagate X through
    random weights so (p, z, q) start self-consistent and residuals start 0."""
    L = len(dims) - 1
    V = X.shape[0]
    keys = jax.random.split(key, L)
    W, b, z, q, p, u = [], [], [], [], [X], []
    cur = X
    for l in range(L):
        Wl = jax.random.normal(keys[l], (dims[l], dims[l + 1]), jnp.float32) \
            * jnp.sqrt(2.0 / dims[l])
        bl = jnp.zeros((dims[l + 1],), jnp.float32)
        zl = cur @ Wl + bl
        W.append(Wl)
        b.append(bl)
        z.append(zl)
        if l < L - 1:
            ql = relu(zl)
            if config.quantize_p and config.grid is not None:
                ql = config.grid.project(ql)
            q.append(ql)
            p.append(ql)
            u.append(jnp.zeros_like(ql))
            cur = ql
    tau = [jnp.asarray(config.tau0, jnp.float32) for _ in range(L)]
    theta = [jnp.asarray(config.tau0, jnp.float32) for _ in range(L)]
    return ADMMState(p, W, b, z, q, u, tau, theta)


def _u_wire(u, u_codecs):
    if u_codecs is None:
        return list(u)
    from repro.comm.codecs import fake_quantize
    return [ul if c is None else fake_quantize(c, ul)
            for c, ul in zip(u_codecs, u)]


def iterate(state: ADMMState, X, labels, label_mask,
            config: ADMMConfig, p_grids: Optional[tuple] = None,
            q_grids: Optional[tuple] = None,
            u_codecs: Optional[tuple] = None) -> tuple:
    """One full Algorithm-1 iteration. Returns (new_state, metrics dict).

    NOTE the k/k+1 bookkeeping: within an iteration the updates are
    sequential across *variable families* (p then W then b then z then q
    then u) but parallel across layers within each family.

    `p_grids` (length L, entry 0 unused) / `q_grids` (length L-1) give each
    layer its own quantization grid — the adaptive bit-width controller
    (repro.comm.controller) re-derives them every schedule change. When
    omitted, every layer uses `config.grid` (the paper's fixed setting).

    `u_codecs` (length L-1) quantizes the *transmitted view* of each dual
    u_l consumed by layer l+1's p/W updates (the forward u wire, fp32 in the
    paper). The stored dual stays exact — Lemma 4 is untouched; only what
    crosses the link is coarsened.

    Runs the matmul-minimal fast path (see module docstring); with an
    equal-width hidden block and homogeneous grids it is additionally
    layer-stacked. ``iterate_reference`` is the naive oracle.
    """
    L = len(state.W)
    if p_grids is None:
        p_grids = (config.grid if config.quantize_p else None,) * L
    if q_grids is None:
        q_grids = (config.grid if config.quantize_q else None,) * (L - 1)
    if config.stack_hidden and _stackable(state, p_grids, q_grids):
        return _iterate_stacked(state, X, labels, label_mask, config,
                                p_grids, q_grids, u_codecs)
    return _iterate_layers(state, X, labels, label_mask, config,
                           p_grids, q_grids, u_codecs)


def _stackable(state: ADMMState, p_grids, q_grids) -> bool:
    """True when layers 1..L-2 share a square [h, h] weight (equal-width
    hidden block) and the per-layer grids are homogeneous over the stacked
    ranges — the preconditions for the vmap fast path."""
    L = len(state.W)
    if L < 4:                       # need >= 2 square layers to win anything
        return False
    h = state.W[1].shape[0]
    if any(state.W[l].shape != (h, h) for l in range(1, L - 1)):
        return False
    if state.W[0].shape[1] != h or state.W[L - 1].shape[0] != h:
        return False
    if len(set(p_grids[1:L - 1])) > 1 or len(set(q_grids)) > 1:
        return False
    return True


def _iterate_layers(state, X, labels, label_mask, config, p_grids, q_grids,
                    u_codecs):
    """Per-layer fast path: residual chaining + incremental backtracking,
    heterogeneous widths/grids allowed."""
    nu, rho = config.nu, config.rho
    uk = config.use_kernels
    decay = config.backtrack_decay
    L = len(state.W)

    p, W, b, z, q, u = (list(state.p), list(state.W), list(state.b),
                        list(state.z), list(state.q), list(state.u))
    tau, theta = list(state.tau), list(state.theta)
    u_wire = _u_wire(u, u_codecs)

    # ---- entry residuals r_l = z_l - p_l W_l - b_l (one fused op each) ----
    r = [sp._residual(p[l], W[l], b[l], z[l], uk) for l in range(L)]

    # ---- p-updates (l = 1..L-1), parallel across layers -----------------
    for l in range(1, L):
        p[l], tau[l], r[l] = sp.update_p(
            p[l], W[l], b[l], z[l], q[l - 1], u_wire[l - 1], nu, rho,
            tau[l] * decay + 1e-6, grid=p_grids[l], r0=r[l], use_kernels=uk)

    # ---- W-updates -------------------------------------------------------
    for l in range(L):
        qp = q[l - 1] if l > 0 else None
        up = u_wire[l - 1] if l > 0 else None
        W[l], theta[l], r[l] = sp.update_W(
            p[l], W[l], b[l], z[l], qp, up, nu, rho,
            theta[l] * decay + 1e-6, first=(l == 0), r0=r[l], use_kernels=uk)

    # ---- b-updates (exact: b⁺ = b + mean r; matmul-free) ------------------
    for l in range(L):
        db = jnp.mean(r[l], axis=0)
        b[l] = b[l] + db
        r[l] = r[l] - db

    # ---- z-updates (a_l = p_l W_l + b_l = z_l - r_l; matmul-free) ---------
    z_old = list(state.z)
    for l in range(L - 1):
        z[l] = sp._zupdate(z[l] - r[l], q[l], z[l], nu, uk)
    z[L - 1] = sp.update_z_last(z[L - 1] - r[L - 1], z[L - 1], labels,
                                label_mask, nu, config.fista_iters,
                                use_kernels=uk)

    # ---- q-updates ----------------------------------------------------------
    dual_res = []
    for l in range(L - 1):
        q[l] = sp.update_q(p[l + 1], u[l], relu(z[l]), nu, rho,
                           grid=q_grids[l])
        # ADMM dual residual s_l = rho ||q^{k+1} - q^k|| (Boyd §3.3): decays
        # as the iterate settles, at ANY grid resolution — unlike the primal
        # residual, which collapses to exactly 0 once p and q share a grid.
        dual_res.append(rho * jnp.linalg.norm(q[l] - state.q[l]))

    # ---- dual updates + residuals --------------------------------------------
    res_sq = jnp.float32(0.0)
    layer_res, cons = [], []
    for l in range(L - 1):
        u[l], rc = sp.update_u(u[l], p[l + 1], q[l], rho)
        cons.append(rc)
        rsq = jnp.vdot(rc, rc)
        res_sq = res_sq + rsq
        layer_res.append(jnp.sqrt(rsq))

    new = ADMMState(p, W, b, z, q, u, tau, theta)
    # objective, reusing the chained residuals: rr_l = r_l + (z⁺_l - z_l)
    obj, _ = sp.ce_value_grad(z[L - 1], labels, label_mask)
    for l in range(L):
        rr = r[l] + (z[l] - z_old[l])
        obj = obj + 0.5 * nu * jnp.vdot(rr, rr)
    for l in range(L - 1):
        gq = q[l] - relu(z[l])
        obj = obj + 0.5 * nu * jnp.vdot(gq, gq)
        obj = obj + jnp.vdot(u[l], cons[l]) + 0.5 * rho * jnp.vdot(cons[l],
                                                                   cons[l])
    metrics = {
        "objective": obj,
        "residual": jnp.sqrt(res_sq),
        # per-boundary primal ||p_{l+1} - q_l|| and dual rho||q^{k+1} - q^k||
        # residuals: the control signals for the adaptive bit-width
        # controller (repro.comm.controller)
        "layer_residuals": (jnp.stack(layer_res) if layer_res
                            else jnp.zeros((0,), jnp.float32)),
        "layer_dual_residuals": (jnp.stack(dual_res) if dual_res
                                 else jnp.zeros((0,), jnp.float32)),
    }
    return new, metrics


def _iterate_stacked(state, X, labels, label_mask, config, p_grids, q_grids,
                     u_codecs):
    """Layer-stacked fast path for the equal-width hidden block (layers
    1..L-2 share [h, h] weights — the paper's large-scale configuration,
    mirroring ``stage_parallel.StackState``). Each variable family is ONE
    vmapped dispatch over the [L_h, ...] stack; the ragged first/last layers
    run individually."""
    nu, rho = config.nu, config.rho
    uk = config.use_kernels
    decay = config.backtrack_decay
    L = len(state.W)
    last = L - 1
    u_wire = _u_wire(state.u, u_codecs)

    # ---- stack the homogeneous block (layers 1..L-2) ----------------------
    ph = jnp.stack(state.p[1:last])
    Wh = jnp.stack(state.W[1:last])
    bh = jnp.stack(state.b[1:last])
    zh = jnp.stack(state.z[1:last])
    qph = jnp.stack(state.q[0:last - 1])        # q_{l-1} for l in 1..L-2
    uph = jnp.stack(u_wire[0:last - 1])
    tauh = jnp.stack(state.tau[1:last])
    thetah = jnp.stack(state.theta[1:last])
    grid_h = p_grids[1]
    q_grid = q_grids[0]

    # ---- entry residuals ---------------------------------------------------
    res_of = functools.partial(sp._residual, use_kernels=uk)
    r0 = sp._residual(state.p[0], state.W[0], state.b[0], state.z[0], uk)
    rh = jax.vmap(res_of)(ph, Wh, bh, zh)
    rl = sp._residual(state.p[last], state.W[last], state.b[last],
                      state.z[last], uk)

    # ---- p-updates: one vmapped solve for the block + the last layer ------
    def p_upd(p_, W_, b_, z_, qp, up, t0, r_):
        return sp.update_p(p_, W_, b_, z_, qp, up, nu, rho, t0,
                           grid=grid_h, r0=r_, use_kernels=uk)

    ph, tauh, rh = jax.vmap(p_upd)(ph, Wh, bh, zh, qph, uph,
                                   tauh * decay + 1e-6, rh)
    p_last, tau_last, rl = sp.update_p(
        state.p[last], state.W[last], state.b[last], state.z[last],
        state.q[last - 1], u_wire[last - 1], nu, rho,
        state.tau[last] * decay + 1e-6, grid=p_grids[last], r0=rl,
        use_kernels=uk)

    # ---- W-updates ---------------------------------------------------------
    W0, theta0, r0 = sp.update_W(
        state.p[0], state.W[0], state.b[0], state.z[0], None, None, nu, rho,
        state.theta[0] * decay + 1e-6, first=True, r0=r0, use_kernels=uk)

    def W_upd(p_, W_, b_, z_, qp, up, t0, r_):
        return sp.update_W(p_, W_, b_, z_, qp, up, nu, rho, t0, first=False,
                           r0=r_, use_kernels=uk)

    Wh, thetah, rh = jax.vmap(W_upd)(ph, Wh, bh, zh, qph, uph,
                                     thetah * decay + 1e-6, rh)
    W_last, theta_last, rl = sp.update_W(
        p_last, state.W[last], state.b[last], state.z[last],
        state.q[last - 1], u_wire[last - 1], nu, rho,
        state.theta[last] * decay + 1e-6, first=False, r0=rl, use_kernels=uk)

    # ---- b-updates (exact, matmul-free) -----------------------------------
    db0 = jnp.mean(r0, axis=0)
    b0, r0 = state.b[0] + db0, r0 - db0
    dbh = jnp.mean(rh, axis=1, keepdims=True)
    bh, rh = bh + dbh[:, 0, :], rh - dbh
    dbl = jnp.mean(rl, axis=0)
    b_last, rl = state.b[last] + dbl, rl - dbl

    # ---- z-updates: hidden layers 0..L-2 in ONE stacked dispatch ----------
    z_old_hid = jnp.stack(state.z[0:last])              # [L-1, V, h]
    a_hid = z_old_hid - jnp.concatenate([r0[None], rh], axis=0)
    q_old = jnp.stack(state.q)                          # [L-1, V, h]
    z_hid = sp._zupdate(a_hid, q_old, z_old_hid, nu, uk)
    z_last = sp.update_z_last(state.z[last] - rl, state.z[last], labels,
                              label_mask, nu, config.fista_iters,
                              use_kernels=uk)

    # ---- q-updates (closed form; elementwise, so the [L-1,V,h] stack goes
    # straight through the per-layer solver) --------------------------------
    u_old = jnp.stack(state.u)
    p_next = jnp.concatenate([ph, p_last[None]], axis=0)    # p_{l+1}, new
    fz = relu(z_hid)
    q_new = sp.update_q(p_next, u_old, fz, nu, rho, grid=q_grid)
    dual_res = rho * jnp.sqrt(jnp.sum((q_new - q_old) ** 2, axis=(1, 2)))

    # ---- dual updates + residuals -----------------------------------------
    u_new, cons = sp.update_u(u_old, p_next, q_new, rho)
    layer_sq = jnp.sum(cons ** 2, axis=(1, 2))
    layer_res = jnp.sqrt(layer_sq)
    res = jnp.sqrt(jnp.sum(layer_sq))

    # ---- objective from the chained residuals -----------------------------
    obj, _ = sp.ce_value_grad(z_last, labels, label_mask)
    rr_hid = jnp.concatenate([r0[None], rh], axis=0) + (z_hid - z_old_hid)
    rr_last = rl + (z_last - state.z[last])
    obj = obj + 0.5 * nu * (jnp.sum(rr_hid ** 2) + jnp.vdot(rr_last, rr_last))
    gq = q_new - fz
    obj = obj + 0.5 * nu * jnp.sum(gq ** 2)
    obj = obj + jnp.sum(u_new * cons) + 0.5 * rho * jnp.sum(cons ** 2)

    new = ADMMState(
        p=[state.p[0]] + list(ph) + [p_last],
        W=[W0] + list(Wh) + [W_last],
        b=[b0] + list(bh) + [b_last],
        z=list(z_hid) + [z_last],
        q=list(q_new),
        u=list(u_new),
        tau=[state.tau[0]] + list(tauh) + [tau_last],
        theta=[theta0] + list(thetah) + [theta_last])
    metrics = {
        "objective": obj,
        "residual": res,
        "layer_residuals": layer_res,
        "layer_dual_residuals": dual_res,
    }
    return new, metrics


def iterate_reference(state: ADMMState, X, labels, label_mask,
                      config: ADMMConfig, p_grids: Optional[tuple] = None,
                      q_grids: Optional[tuple] = None,
                      u_codecs: Optional[tuple] = None) -> tuple:
    """The pre-optimization Algorithm-1 iteration: naive per-trial φ
    re-evaluation, per-layer matmuls for b/z, no kernel dispatch. Ground
    truth for the fast-path equivalence tests and the bench baseline."""
    nu, rho = config.nu, config.rho
    L = len(state.W)
    if p_grids is None:
        p_grids = (config.grid if config.quantize_p else None,) * L
    if q_grids is None:
        q_grids = (config.grid if config.quantize_q else None,) * (L - 1)

    p, W, b, z, q, u = (list(state.p), list(state.W), list(state.b),
                        list(state.z), list(state.q), list(state.u))
    tau, theta = list(state.tau), list(state.theta)
    u_wire = _u_wire(u, u_codecs)

    for l in range(1, L):
        p[l], tau[l] = sp.update_p_reference(
            p[l], W[l], b[l], z[l], q[l - 1], u_wire[l - 1], nu, rho,
            tau[l] * config.backtrack_decay + 1e-6, grid=p_grids[l])

    for l in range(L):
        qp = q[l - 1] if l > 0 else None
        up = u_wire[l - 1] if l > 0 else None
        W[l], theta[l] = sp.update_W_reference(
            p[l], W[l], b[l], z[l], qp, up, nu, rho,
            theta[l] * config.backtrack_decay + 1e-6, first=(l == 0))

    for l in range(L):
        b[l] = sp.update_b(p[l], W[l], z[l])

    for l in range(L - 1):
        a = sp.linear(p[l], W[l], b[l])
        z[l] = sp.update_z_hidden(a, q[l], z[l], nu)
    aL = sp.linear(p[L - 1], W[L - 1], b[L - 1])
    z[L - 1] = sp.update_z_last_reference(aL, z[L - 1], labels, label_mask,
                                          nu, config.fista_iters)

    dual_res = []
    for l in range(L - 1):
        q[l] = sp.update_q(p[l + 1], u[l], relu(z[l]), nu, rho,
                           grid=q_grids[l])
        dual_res.append(rho * jnp.linalg.norm(q[l] - state.q[l]))

    res_sq = jnp.float32(0.0)
    layer_res = []
    for l in range(L - 1):
        u[l], rc = sp.update_u(u[l], p[l + 1], q[l], rho)
        rsq = jnp.vdot(rc, rc)
        res_sq = res_sq + rsq
        layer_res.append(jnp.sqrt(rsq))

    new = ADMMState(p, W, b, z, q, u, tau, theta)
    metrics = {
        "objective": lagrangian(new, labels, label_mask, config),
        "residual": jnp.sqrt(res_sq),
        "layer_residuals": (jnp.stack(layer_res) if layer_res
                            else jnp.zeros((0,), jnp.float32)),
        "layer_dual_residuals": (jnp.stack(dual_res) if dual_res
                                 else jnp.zeros((0,), jnp.float32)),
    }
    return new, metrics


def lagrangian(s: ADMMState, labels, label_mask, config: ADMMConfig):
    """L_ρ (Section III-B)."""
    nu, rho = config.nu, config.rho
    L = len(s.W)
    val, _ = sp.ce_value_grad(s.z[L - 1], labels, label_mask)
    for l in range(L):
        r = s.z[l] - sp.linear(s.p[l], s.W[l], s.b[l])
        val = val + 0.5 * nu * jnp.vdot(r, r)
    for l in range(L - 1):
        g = s.q[l] - relu(s.z[l])
        val = val + 0.5 * nu * jnp.vdot(g, g)
        d = s.p[l + 1] - s.q[l]
        val = val + jnp.vdot(s.u[l], d) + 0.5 * rho * jnp.vdot(d, d)
    return val


def forward_accuracy(s: ADMMState, X, labels, mask) -> jax.Array:
    """Inference accuracy of the trained MLP (standard forward pass)."""
    h = X
    L = len(s.W)
    for l in range(L - 1):
        h = relu(h @ s.W[l] + s.b[l])
    logits = h @ s.W[L - 1] + s.b[L - 1]
    pred = jnp.argmax(logits, axis=-1)
    correct = jnp.sum((pred == labels) * mask)
    return correct / jnp.maximum(jnp.sum(mask), 1.0)


def comm_bytes_per_iteration(dims: Sequence[int], V: int,
                             config: ADMMConfig) -> float:
    """DEPRECATED shim — wire-byte accounting lives in ``repro.comm.ledger``
    (the CommLedger is the single source of truth; benchmarks read ONLY the
    ledger). Delegates to ``record_admm_iteration`` on a scratch ledger."""
    warnings.warn(
        "pdadmm.comm_bytes_per_iteration is deprecated: record the traffic "
        "on a repro.comm.ledger.CommLedger (record_admm_iteration) and read "
        "totals from the ledger instead.",
        DeprecationWarning, stacklevel=2)
    from repro.comm.codecs import codec_for_grid
    from repro.comm.ledger import admm_bytes_per_iteration
    return float(admm_bytes_per_iteration(
        dims, V,
        codec_for_grid(config.grid if config.quantize_p else None),
        codec_for_grid(config.grid if config.quantize_q else None)))


def calibrate_grid(key, X, dims, bits: int, margin_frac: float = 0.05):
    """Fit a b-bit uniform grid to this model's activation range (sampled at
    a forward-consistent init) — the analogue of the paper choosing
    Δ = {-1..20} to cover ITS activations."""
    state = init_state(key, X, dims, ADMMConfig())
    vals = jnp.concatenate([q.ravel()[:20_000] for q in state.q] or
                           [X.ravel()[:20_000]])
    lo, hi = float(jnp.min(vals)), float(jnp.max(vals))
    margin = (hi - lo) * margin_frac
    return uniform_grid(bits, lo - margin, hi + margin)


# ---------------------------------------------------------------------------
# Scan-driven training driver
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("step_fn", "length"))
def _scan_chunk(state, args, *, step_fn, length):
    def body(c, _):
        return step_fn(c, *args)
    return jax.lax.scan(body, state, None, length=length)


def run_chunked(step_fn, state, args, n_iters: int, chunk: int = 32):
    """Run ``n_iters`` iterations of ``step_fn(state, *args) -> (state, m)``
    as ``lax.scan`` chunks: the per-iteration metrics stay on device inside
    each chunk (stacked history), so the host syncs once per chunk instead
    of once per iteration.

    ``step_fn`` is a *static* jit argument (keyed by identity), so repeated
    calls with the same callable — e.g. ``train_adaptive`` re-entering every
    control step with its per-schedule cached partial — reuse the compiled
    scan; at most two scan lengths compile per callable (``chunk`` and the
    final remainder). The carry is NOT donated: ``init_state`` aliases
    p[l+1] and q[l] to one buffer (forward-consistent init), and XLA rejects
    donating the same buffer twice; the scan loop reuses carry buffers
    internally anyway.

    Each chunk opens two host spans for the profiler:
    ``pdadmm.chunk.dispatch`` around the ``_scan_chunk`` call (tracing and
    compile or cache load on a first call, enqueue after that) and
    ``pdadmm.chunk.fetch`` around the ``device_get`` that waits for the
    chunk. With no profiler running each costs under a microsecond.

    Returns ``(state, metrics)`` with metrics stacked host-side over all
    ``n_iters`` (numpy arrays, leading axis = iteration); an empty dict when
    ``n_iters <= 0``.
    """
    import numpy as np

    if n_iters <= 0:
        return state, {}
    chunk = max(1, min(int(chunk), int(n_iters)))
    pieces, done = [], 0
    while done < n_iters:
        c = min(chunk, n_iters - done)
        with jax.profiler.TraceAnnotation("pdadmm.chunk.dispatch"):
            state, ms = _scan_chunk(state, args, step_fn=step_fn, length=c)
        with jax.profiler.TraceAnnotation("pdadmm.chunk.fetch"):
            pieces.append(jax.device_get(ms))
        done += c
    metrics = {k: np.concatenate([piece[k] for piece in pieces])
               for k in pieces[0]}
    return state, metrics


def train(key, X, labels, masks, dims, config: ADMMConfig, epochs: int,
          *, jit: bool = True, callback=None, chunk: int = 32):
    """Run `epochs` iterations; returns (state, history dict of arrays).

    The default driver is a chunked ``lax.scan`` (one host transfer per
    ``chunk`` iterations — no per-epoch device→host sync). A ``callback``
    needs the state on host every epoch, so providing one (or ``jit=False``)
    falls back to the legacy per-epoch Python loop.
    """
    state = init_state(key, X, dims, config)
    hist = {"objective": [], "residual": [], "val_acc": [], "test_acc": []}
    if callback is None and jit:
        state, ms = run_chunked(
            functools.partial(iterate, config=config), state,
            (X, labels, masks["train"]), epochs, chunk=chunk)
        hist["objective"] = [float(x) for x in ms.get("objective", [])]
        hist["residual"] = [float(x) for x in ms.get("residual", [])]
    else:
        step = jax.jit(functools.partial(iterate, config=config)) if jit \
            else functools.partial(iterate, config=config)
        for e in range(epochs):
            state, m = step(state, X, labels, masks["train"])
            hist["objective"].append(float(m["objective"]))
            hist["residual"].append(float(m["residual"]))
            if callback is not None:
                callback(e, state, m)
    hist["val_acc"].append(float(forward_accuracy(state, X, labels, masks["val"])))
    hist["test_acc"].append(float(forward_accuracy(state, X, labels, masks["test"])))
    return state, hist
