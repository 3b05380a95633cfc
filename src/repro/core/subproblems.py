"""The six pdADMM-G subproblem solvers (Appendix A/B of the paper).

Layout convention: node-major. p_l, q_l, z_l, u_l are [V, n] (V = #nodes),
W_l is [n_in, n_out], b_l is [n_out]. The linear map is z = p @ W + b.
(The paper writes the transposed layout; the math is identical.)

Every solver is a pure jit-able function of single-layer tensors, shared by
the single-host reference loop (`pdadmm.py`), the stage-parallel shard_map
runtime (`stage_parallel.py`), and the Pallas-accelerated path (`kernels/`).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.quantize import QuantGrid
from repro.kernels.fista_zlast import prox_carry_weight


def linear(p, W, b):
    return p @ W + b


def phi_first(p, W, b, z, nu):
    """φ(p_1, W_1, b_1, z_1) = (ν/2)||z - Wp - b||² (first layer: p = X fixed)."""
    r = z - linear(p, W, b)
    return 0.5 * nu * jnp.vdot(r, r)


def phi(p, W, b, z, q_prev, u_prev, nu, rho):
    """φ(p_l, W_l, b_l, z_l, q_{l-1}, u_{l-1}) for l >= 2."""
    r = z - linear(p, W, b)
    d = p - q_prev
    return (0.5 * nu * jnp.vdot(r, r) + jnp.vdot(u_prev, d)
            + 0.5 * rho * jnp.vdot(d, d))


def grad_p(p, W, b, z, q_prev, u_prev, nu, rho):
    """∇_p φ = -ν (z - pW - b) Wᵀ + u + ρ(p - q)."""
    r = z - linear(p, W, b)
    return -nu * (r @ W.T) + u_prev + rho * (p - q_prev)


def grad_W(p, W, b, z, nu):
    """∇_W φ = -ν pᵀ (z - pW - b)."""
    r = z - linear(p, W, b)
    return -nu * (p.T @ r)


# ---------------------------------------------------------------------------
# Backtracking quadratic-approximation steps (p- and W-updates)
#
# The accept test at trial τ is  φ(x⁺) <= U(x⁺;τ) = φ0 + gᵀd + (τ/2)||d||²
# (d = x⁺ - x0, with the same 1e-6 relative slack everywhere). Two engines:
#
#   * `_backtrack` — the naive engine: re-evaluates φ on the full tensors
#     every trial (a fresh [V,n]x[n,m] matmul per doubling). Kept as the
#     ground-truth oracle for the `update_*_reference` pre-optimization
#     solvers and the property tests.
#   * `_backtrack_scalar` — the incremental engine for the unprojected step
#     x⁺ = x0 - g/τ: φ is exactly quadratic along -g, so every trial reduces
#     to three cached scalars (φ0, ||g||², the curvature gᵀHg) and the whole
#     search runs matmul-free. Accepts batched (per-layer vector) inputs:
#     each component doubles independently until its own test passes.
#
# The projected (quantized) step is NOT linear in 1/τ, so `update_p` with a
# grid evaluates the exact delta-residual form φ(x⁺) = (ν/2)||r0 - dW||² + …
# per trial through `ops.backtrack_resnorm` — one fused kernel per trial
# instead of recomputing z - x⁺W - b from scratch.
# ---------------------------------------------------------------------------

def _backtrack(x0, g, phi_at, phi0, t0, *, grid: Optional[QuantGrid],
               max_doublings: int = 12):
    """Find τ = t0·2^j s.t. φ(x⁺) <= U(x⁺;τ) = φ(x0) + gᵀ(x⁺-x0) + τ/2||x⁺-x0||².

    x⁺ = proj(x0 - g/τ) (projection only in the quantized variant).
    Runs as a lax.while_loop — jit-safe, bounded.
    """
    def step(t):
        x = x0 - g / t
        if grid is not None:
            x = grid.project(x)
        return x

    def cond(state):
        t, j = state
        x = step(t)
        d = x - x0
        u_val = phi0 + jnp.vdot(g, d) + 0.5 * t * jnp.vdot(d, d)
        return jnp.logical_and(phi_at(x) > u_val + 1e-6 * jnp.abs(u_val),
                               j < max_doublings)

    def body(state):
        t, j = state
        return t * 2.0, j + 1

    t_final, _ = jax.lax.while_loop(cond, body, (jnp.asarray(t0, jnp.float32),
                                                 jnp.asarray(0, jnp.int32)))
    return step(t_final), t_final


def _backtrack_scalar(phi0, g_sq, curv, t0, *, max_doublings: int = 12):
    """Matmul-free backtracking on the exact quadratic restriction of φ along
    -g:  φ(x0 - g/τ) = φ0 - ||g||²/τ + gᵀHg/(2τ²),  U(τ) = φ0 - ||g||²/(2τ).

    Same accept test and doubling schedule as `_backtrack`, evaluated on
    three scalars. All inputs may be same-shaped vectors (one entry per
    stacked layer); each entry doubles until its own accept test passes.
    """
    t0 = jnp.asarray(t0, jnp.float32)

    def needs_doubling(t):
        s = 1.0 / t
        phi_x = phi0 - s * g_sq + 0.5 * s * s * curv
        u_val = phi0 - 0.5 * s * g_sq
        return phi_x > u_val + 1e-6 * jnp.abs(u_val)

    def cond(state):
        t, j = state
        return jnp.logical_and(jnp.any(needs_doubling(t)), j < max_doublings)

    def body(state):
        t, j = state
        return jnp.where(needs_doubling(t), t * 2.0, t), j + 1

    t_final, _ = jax.lax.while_loop(cond, body,
                                    (t0, jnp.asarray(0, jnp.int32)))
    return t_final


def _dot(a, b):
    """Scalar <a, b> as an elementwise multiply-reduce. Unlike jnp.vdot
    this never lowers to dot_general, keeping the fast solvers' jaxprs at
    exactly the two genuine matmuls (asserted by the trace-level test)."""
    return jnp.sum(a * b)


# -- kernel-dispatch helpers (jnp fallback when use_kernels=False) -----------

def _residual(p, W, b, z, use_kernels: bool):
    """r = z - (pW + b), the quantity every solver in the family re-reads."""
    if use_kernels:
        from repro.kernels import ops
        return ops.fused_linear(p, W, b, z, mode="residual")
    return z - linear(p, W, b)


def _pgrad(r0, W, u_prev, p, q_prev, nu, rho, use_kernels: bool):
    if use_kernels:
        from repro.kernels import ops
        return ops.admm_pgrad(r0, W, u_prev, p, q_prev,
                              nu=float(nu), rho=float(rho))
    return -nu * (r0 @ W.T) + u_prev + rho * (p - q_prev)


def _matmul(a, bmat, use_kernels: bool):
    if use_kernels:
        from repro.kernels import ops
        return ops.fused_linear(a, bmat, jnp.zeros((bmat.shape[1],), a.dtype),
                                mode="linear")
    return a @ bmat


def _resnorm_sq(r0, d, W, use_kernels: bool):
    if use_kernels:
        from repro.kernels import ops
        return ops.backtrack_resnorm(r0, d, W)
    r = r0 - d @ W
    return jnp.vdot(r, r)


def _zupdate(a, q, z_old, nu, use_kernels: bool):
    """Eq.-6 ReLU z-update dispatch (the minimizer is ν-independent, so the
    kernel takes no ν). Shared by the single-host loop, the stage-parallel
    runtime and the benchmark — one dispatch decision for all three."""
    if use_kernels:
        from repro.kernels import ops
        return ops.relu_zupdate(a, q, z_old)
    return update_z_hidden(a, q, z_old, nu)


def update_p(p, W, b, z, q_prev, u_prev, nu, rho, tau0,
             grid: Optional[QuantGrid] = None, r0=None,
             use_kernels: bool = False, max_doublings: int = 12):
    """p-subproblem (Eq. 3 / Eq. 10), matmul-minimal.

    Returns ``(p_new, tau_used, r_new)`` with ``r_new = z - p_new W - b`` so
    the caller can chain the residual into the W-/b-/z-updates without ever
    recomputing a [V,n]x[n,m] product. Pass ``r0 = z - pW - b`` (e.g. from
    ``ops.fused_linear(mode="residual")``) to skip the entry matmul: the
    unprojected path then costs exactly 2 matmuls (r0 Wᵀ for the gradient,
    gW for the curvature/residual axpy) regardless of trial count.
    """
    if r0 is None:
        r0 = _residual(p, W, b, z, use_kernels)
    g = _pgrad(r0, W, u_prev, p, q_prev, nu, rho, use_kernels)
    d0 = p - q_prev
    phi0 = (0.5 * nu * _dot(r0, r0) + _dot(u_prev, d0)
            + 0.5 * rho * _dot(d0, d0))

    if grid is None:
        # x⁺(τ) = p - g/τ is linear in 1/τ: the residual moves along the
        # cached direction gW and every trial is scalar arithmetic.
        gW = _matmul(g, W, use_kernels)
        g_sq = _dot(g, g)
        curv = nu * _dot(gW, gW) + rho * g_sq          # gᵀ(ν WWᵀ + ρI)g
        tau = _backtrack_scalar(phi0, g_sq, curv, tau0,
                                max_doublings=max_doublings)
        return p - g / tau, tau, r0 + gW / tau

    # Projected path: x⁺ = proj(p - g/τ) is only piecewise linear in 1/τ,
    # so each trial evaluates the exact delta-residual φ — one fused
    # ||r0 - dW||² contraction per trial instead of a fresh z - x⁺W - b.
    def trial_d(t):
        return grid.project(p - g / t) - p

    def cond(state):
        t, j = state
        d = trial_d(t)
        dq = d + d0
        phi_x = (0.5 * nu * _resnorm_sq(r0, d, W, use_kernels)
                 + jnp.vdot(u_prev, dq) + 0.5 * rho * jnp.vdot(dq, dq))
        u_val = phi0 + jnp.vdot(g, d) + 0.5 * t * jnp.vdot(d, d)
        return jnp.logical_and(phi_x > u_val + 1e-6 * jnp.abs(u_val),
                               j < max_doublings)

    def body(state):
        t, j = state
        return t * 2.0, j + 1

    tau, _ = jax.lax.while_loop(cond, body, (jnp.asarray(tau0, jnp.float32),
                                             jnp.asarray(0, jnp.int32)))
    d = trial_d(tau)
    if use_kernels:
        from repro.kernels import ops
        r_new = ops.fused_linear(d, W, jnp.zeros((W.shape[1],), d.dtype),
                                 r0, mode="residual")
    else:
        r_new = r0 - d @ W
    return p + d, tau, r_new


def update_W(p, W, b, z, q_prev, u_prev, nu, rho, theta0, *, first: bool,
             r0=None, use_kernels: bool = False, max_doublings: int = 12):
    """W-subproblem (Eq. 4), matmul-minimal.

    Returns ``(W_new, theta_used, r_new)`` with ``r_new = z - p W_new - b``.
    With ``r0`` supplied the solve is exactly 2 matmuls (pᵀr0 for the
    gradient, pg for the curvature/residual axpy) regardless of trial count.
    The dual terms of φ are constants w.r.t. W; they enter only φ0 (they
    scale the relative accept slack, matching the naive engine exactly).
    """
    if r0 is None:
        r0 = _residual(p, W, b, z, use_kernels)
    g = -nu * (p.T @ r0)
    pg = _matmul(p, g, use_kernels)
    phi0 = 0.5 * nu * _dot(r0, r0)
    if not first:
        d0 = p - q_prev
        phi0 = phi0 + _dot(u_prev, d0) + 0.5 * rho * _dot(d0, d0)
    g_sq = _dot(g, g)
    curv = nu * _dot(pg, pg)                           # gᵀ(ν pᵀp ⊗ I)g
    theta = _backtrack_scalar(phi0, g_sq, curv, theta0,
                              max_doublings=max_doublings)
    return W - g / theta, theta, r0 + pg / theta


# -- pre-optimization reference solvers (naive full-tensor backtracking) -----

def update_p_reference(p, W, b, z, q_prev, u_prev, nu, rho, tau0,
                       grid: Optional[QuantGrid] = None):
    """The pre-fast-path p-subproblem: fresh matmul per backtracking trial.
    Ground truth for the incremental engine; returns (p_new, tau_used)."""
    g = grad_p(p, W, b, z, q_prev, u_prev, nu, rho)
    phi0 = phi(p, W, b, z, q_prev, u_prev, nu, rho)
    phi_at = lambda x: phi(x, W, b, z, q_prev, u_prev, nu, rho)
    return _backtrack(p, g, phi_at, phi0, tau0, grid=grid)


def update_W_reference(p, W, b, z, q_prev, u_prev, nu, rho, theta0, *,
                       first: bool):
    """The pre-fast-path W-subproblem. Returns (W_new, theta_used)."""
    g = grad_W(p, W, b, z, nu)
    if first:
        phi0 = phi_first(p, W, b, z, nu)
        phi_at = lambda Wx: phi_first(p, Wx, b, z, nu)
    else:
        phi0 = phi(p, W, b, z, q_prev, u_prev, nu, rho)
        phi_at = lambda Wx: phi(p, Wx, b, z, q_prev, u_prev, nu, rho)
    return _backtrack(W, g, phi_at, phi0, theta0, grid=None)


def update_b(p, W, z):
    """Exact minimizer of (ν/2)||z - pW - b||² over b: column mean of (z - pW).

    (The paper takes a 1/ν gradient step; the exact solve satisfies the same
    descent inequality — see DESIGN.md §7.)
    """
    return jnp.mean(z - p @ W, axis=0)


# ---------------------------------------------------------------------------
# z-updates
# ---------------------------------------------------------------------------

def update_z_hidden(a, q, z_old, nu):
    """Closed-form ReLU solution of Eq. (6):
       min_z (ν/2)[(z-a)² + (q-relu(z))² + (z-z_old)²]  — elementwise.
    Branch z<=0: z = min((a+z_old)/2, 0); branch z>=0: z = max((a+q+z_old)/3, 0);
    pick the branch with the lower objective value.
    """
    zn = jnp.minimum((a + z_old) / 2.0, 0.0)
    zp = jnp.maximum((a + q + z_old) / 3.0, 0.0)

    def obj(zz):
        return ((zz - a) ** 2 + (q - jnp.maximum(zz, 0.0)) ** 2
                + (zz - z_old) ** 2)

    return jnp.where(obj(zn) <= obj(zp), zn, zp)


def ce_value_grad(z, labels, label_mask):
    """Summed softmax cross-entropy over labeled nodes. z: [V, C]."""
    logp = jax.nn.log_softmax(z, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    val = jnp.sum(nll * label_mask)
    grad = (jax.nn.softmax(z, axis=-1)
            - jax.nn.one_hot(labels, z.shape[-1])) * label_mask[:, None]
    return val, grad


def ce_grad_cols(z, labels, label_mask, n_classes: Optional[int] = None):
    """Masked-CE gradient on z[:, :n_classes], zero-padded back to z's width
    — the risk gradient of BOTH z_L layouts: the single-host solve
    (n_classes == width, pad is a no-op) and the distributed head-folded
    layout where only the first C of h columns carry logits."""
    C = z.shape[-1] if n_classes is None else n_classes
    zc = z[:, :C]
    g = (jax.nn.softmax(zc, axis=-1)
         - jax.nn.one_hot(labels, C)) * label_mask[:, None]
    if C == z.shape[-1]:
        return g
    return jnp.pad(g, ((0, 0), (0, z.shape[-1] - C)))


def fista_prox(g_grad, z_old, step, n_iters: int):
    """The generic FISTA loop  z⁺ = y − step·g_grad(y)  with Nesterov
    momentum — the ONE implementation every z_L solver shares (the CE jnp
    oracle below, `block_admm`'s arbitrary-risk solve, and the reference).
    Same iteration map as the fused kernel's unrolled dispatches."""
    def body(i, carry):
        z_prev, z_cur, t = carry
        t_new = (1.0 + jnp.sqrt(1.0 + 4.0 * t * t)) / 2.0
        y = z_cur + ((t - 1.0) / t_new) * (z_cur - z_prev)
        return z_cur, y - step * g_grad(y), t_new

    _, z_fin, _ = jax.lax.fori_loop(0, n_iters, body,
                                    (z_old, z_old - step * g_grad(z_old), 1.0))
    return z_fin


def fista_ce(a, z_old, labels, label_mask, nu, n_iters: int = 15,
             n_classes: Optional[int] = None):
    """Pure-jnp z_L solve: FISTA on min_z R(z;y) + (ν/2)||z − a||², R the
    masked CE over z[:, :n_classes]. This is the `ref` side of the
    `ops.fista_zlast` dispatch (`kernels/ref.py` delegates here)."""
    step = 1.0 / (1.0 + nu)

    def g_grad(z):
        return ce_grad_cols(z, labels, label_mask, n_classes) + nu * (z - a)

    return fista_prox(g_grad, z_old, step, n_iters)


def update_z_last(a, z_old, labels, label_mask, nu, n_iters: int = 15,
                  n_classes: Optional[int] = None, use_kernels: bool = True):
    """FISTA for min_z R(z;y) + (ν/2)||z - a||² (Eq. 7). R = summed CE.

    ∇R is 1-Lipschitz (softmax Jacobian ≼ I), so step = 1/(1+ν).

    Dispatches through ``ops.fista_zlast`` (one fused Pallas kernel per
    FISTA iteration under the `REPRO_KERNELS` policy); ``use_kernels=False``
    stays on the local jnp loop. ``update_z_last_reference`` keeps the
    pre-kernel code as the ground-truth oracle.

    With ``n_classes`` C below the width (the head-folded layout), FISTA
    runs on the C logit columns only. The other columns see no softmax and
    a zero one-hot, so their gradient is ν(y − a) and the solve leaves them
    at α·z_old + (1 − α)·a, α = ``prox_carry_weight(n_iters, ν)``: the same
    map as iterating them, rounded once instead of per step.
    """
    C = a.shape[-1] if n_classes is None else n_classes
    if C < a.shape[-1]:
        alpha = prox_carry_weight(n_iters, float(nu))
        zc = update_z_last(a[:, :C], z_old[:, :C], labels, label_mask, nu,
                           n_iters, use_kernels=use_kernels)
        rest = alpha * z_old[:, C:] + (1.0 - alpha) * a[:, C:]
        return jnp.concatenate([zc, rest], axis=1)
    if use_kernels:
        from repro.kernels import ops
        return ops.fista_zlast(a, z_old, labels, label_mask, nu=nu,
                               n_iters=n_iters)
    return fista_ce(a, z_old, labels, label_mask, nu, n_iters)


def update_z_last_reference(a, z_old, labels, label_mask, nu,
                            n_iters: int = 15):
    """The pre-kernel z_L solve (kept verbatim): per-iteration jnp dispatch
    chain through `ce_value_grad`. Ground truth for the fused kernel's
    differential battery and the `iterate_reference` oracle."""
    step = 1.0 / (1.0 + nu)

    def g_grad(z):
        _, gr = ce_value_grad(z, labels, label_mask)
        return gr + nu * (z - a)

    def body2(i, carry):
        z_prev, z_cur, t = carry
        t_new = (1.0 + jnp.sqrt(1.0 + 4.0 * t * t)) / 2.0
        y = z_cur + ((t - 1.0) / t_new) * (z_cur - z_prev)
        z_next = y - step * g_grad(y)
        return z_cur, z_next, t_new

    z0 = z_old
    _, z_fin, _ = jax.lax.fori_loop(0, n_iters, body2,
                                    (z0, z0 - step * g_grad(z0), 1.0))
    return z_fin


def update_q(p_next, u, fz, nu, rho, grid: Optional[QuantGrid] = None):
    """Closed form (Eq. 8): q = (ρ p_{l+1} + u_l + ν f(z_l)) / (ρ+ν).
    Optional projection = the paper's p&q-quantized variant (Appendix B)."""
    q = (rho * p_next + u + nu * fz) / (rho + nu)
    return grid.project(q) if grid is not None else q


def update_u(u, p_next, q, rho):
    """Dual ascent (Eq. 9): u += ρ (p_{l+1} - q_l). Returns (u_new, residual)."""
    r = p_next - q
    return u + rho * r, r
