"""Plain reference of pdADMM-G and pdADMM-G-Q on a stack of equal-width layers.

Written from the paper's Algorithm 1 and update equations (arXiv 2105.09837,
Eqs. 3-9; Appendix B for the projected p-update), with the choices the
stage-parallel program states in its docstrings: the input is projected to
the hidden width h, the classifier head is folded into the first C columns of
the last layer, b is solved exactly (b += mean of the residual), the p- and
W-steps backtrack from tau0 by doubling (at most 12 times) on the test
phi(x+) <= phi0 + <g, x+ - x> + tau/2 ||x+ - x||^2 with a 1e-6 relative
slack, and the last layer's z is solved by FISTA with step 1 / (1 + nu).

Every layer of one iteration reads only its neighbours' values from the
previous iteration, so the reference walks the layers one at a time and keeps
the old and the new state: it needs no more than twice the state and one
layer's temporaries on one device. It imports nothing of the program.

``dtype=float32`` computes every contraction at precision HIGHEST. The
control, ``dtype=bfloat16``, keeps the state and every intermediate in
bfloat16: the precision below the configuration's float32.
"""
from __future__ import annotations

import functools
from typing import List, NamedTuple, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
MAX_DOUBLINGS = 12
SLACK = 1e-6


class Grid(NamedTuple):
    lo: float
    step: float
    n_levels: int

    def project(self, x):
        ix = jnp.clip(jnp.round((x - self.lo) / self.step), 0,
                      self.n_levels - 1)
        return (self.lo + ix * self.step).astype(x.dtype)


class Params(NamedTuple):
    nu: float
    rho: float
    tau0: float
    fista_iters: int
    n_classes: int
    p_grid: Optional[Grid]
    q_grid: Optional[Grid]


class Layer(NamedTuple):
    p: jax.Array
    W: jax.Array
    b: jax.Array
    z: jax.Array
    q: jax.Array
    u: jax.Array


def _mm(a, b):
    if a.dtype == jnp.float32:
        return jnp.dot(a, b, precision=HIGHEST)
    return jnp.dot(a, b, preferred_element_type=a.dtype)


def _double_until(accept, t0):
    """Smallest t = t0 * 2^j (j <= MAX_DOUBLINGS) with accept(t) true."""
    def cond(c):
        t, j = c
        return jnp.logical_and(jnp.logical_not(accept(t)), j < MAX_DOUBLINGS)

    t, _ = jax.lax.while_loop(cond, lambda c: (c[0] * 2.0, c[1] + 1),
                              (jnp.asarray(t0, jnp.float32),
                               jnp.asarray(0, jnp.int32)))
    return t


def _ray_step(phi0, g_sq, curv, tau0):
    """Backtracking along x+ = x - g/t for a quadratic phi: phi(x+) =
    phi0 - ||g||^2/t + g'Hg/(2t^2) and the bound is phi0 - ||g||^2/(2t)."""
    phi0, g_sq, curv = (v.astype(jnp.float32) for v in (phi0, g_sq, curv))

    def accept(t):
        s = 1.0 / t
        phi_x = phi0 - s * g_sq + 0.5 * s * s * curv
        bound = phi0 - 0.5 * s * g_sq
        return phi_x <= bound + SLACK * jnp.abs(bound)

    return _double_until(accept, tau0)


def _relu(x):
    return jnp.maximum(x, 0.0)


def _z_hidden(a, q, z_old):
    """argmin_z (z-a)^2 + (q-relu z)^2 + (z-z_old)^2, elementwise (Eq. 6)."""
    zn = jnp.minimum((a + z_old) / 2.0, 0.0)
    zp = jnp.maximum((a + q + z_old) / 3.0, 0.0)
    obj = lambda v: (v - a) ** 2 + (q - _relu(v)) ** 2 + (v - z_old) ** 2
    return jnp.where(obj(zn) <= obj(zp), zn, zp)


def _z_last(a, z_old, labels, mask, nu, n_iters, n_classes):
    """FISTA on CE(z[:, :C]) over the labelled rows + nu/2 ||z - a||^2."""
    step = 1.0 / (1.0 + nu)
    onehot = jax.nn.one_hot(labels, n_classes, dtype=a.dtype)

    def grad(z):
        zc = z[:, :n_classes]
        g = (jax.nn.softmax(zc, axis=-1) - onehot) * mask[:, None]
        g = jnp.pad(g, ((0, 0), (0, z.shape[1] - n_classes)))
        return g + nu * (z - a)

    z_prev, z_cur, t = z_old, z_old - step * grad(z_old), 1.0
    for _ in range(n_iters):
        t_new = (1.0 + (1.0 + 4.0 * t * t) ** 0.5) / 2.0
        y = z_cur + ((t - 1.0) / t_new) * (z_cur - z_prev)
        z_prev, z_cur, t = z_cur, y - step * grad(y), t_new
    return z_cur


@functools.partial(jax.jit, static_argnames=("prm", "first", "last"))
def primal(cur: Layer, q_prev, u_prev, labels, mask, *, prm: Params,
           first: bool, last: bool):
    """p, W, b and z of one layer from the previous iterate (Eqs. 3-7)."""
    nu, rho = prm.nu, prm.rho
    p, W, b, z = cur.p, cur.W, cur.b, cur.z
    r = z - (_mm(p, W) + b)
    d0 = p - q_prev
    if not first:
        g = -nu * _mm(r, W.T) + u_prev + rho * d0
        phi0 = 0.5 * nu * jnp.sum(r * r) + jnp.sum(u_prev * d0) \
            + 0.5 * rho * jnp.sum(d0 * d0)
        if prm.p_grid is None:
            gW = _mm(g, W)
            g_sq = jnp.sum(g * g)
            tau = _ray_step(phi0, g_sq, nu * jnp.sum(gW * gW) + rho * g_sq,
                            prm.tau0)
            p = p - (g / tau).astype(p.dtype)
            r = r + (gW / tau).astype(r.dtype)
        else:
            grid = prm.p_grid
            trial = lambda t: grid.project(p - (g / t).astype(p.dtype)) - p

            def accept(t):
                d = trial(t)
                dq = d + d0
                res = r - _mm(d, W)
                phi_x = (0.5 * nu * jnp.sum(res * res) + jnp.sum(u_prev * dq)
                         + 0.5 * rho * jnp.sum(dq * dq)).astype(jnp.float32)
                bound = (phi0 + jnp.sum(g * d)).astype(jnp.float32) \
                    + 0.5 * t * jnp.sum(d * d).astype(jnp.float32)
                return phi_x <= bound + SLACK * jnp.abs(bound)

            d = trial(_double_until(accept, prm.tau0))
            p, r = p + d, r - _mm(d, W)
        d0 = p - q_prev
    # W-step (Eq. 4): the dual terms are constants in W and enter phi0 only
    gw = -nu * _mm(p.T, r)
    pg = _mm(p, gw)
    phi0 = 0.5 * nu * jnp.sum(r * r) + jnp.sum(u_prev * d0) \
        + 0.5 * rho * jnp.sum(d0 * d0)
    gw_sq = jnp.sum(gw * gw)
    theta = _ray_step(phi0, gw_sq, nu * jnp.sum(pg * pg), prm.tau0)
    W = W - (gw / theta).astype(W.dtype)
    r = r + (pg / theta).astype(r.dtype)
    # b-step: exact minimiser
    db = jnp.mean(r, axis=0)
    b, r = b + db, r - db
    a = z - r                                   # = p W + b
    if last:
        z = _z_last(a, z, labels, mask, nu, prm.fista_iters, prm.n_classes)
    else:
        z = _z_hidden(a, cur.q, z)
    return p, W, b, z


@functools.partial(jax.jit, static_argnames=("prm",))
def dual(q, u, z_new, p_next_new, *, prm: Params):
    """q (Eq. 8) and u (Eq. 9) of a layer below the last; returns the layer's
    squared primal residual ||p_{l+1} - q_l||^2 too."""
    nu, rho = prm.nu, prm.rho
    q = (rho * p_next_new + u + nu * _relu(z_new)) / (rho + nu)
    if prm.q_grid is not None:
        q = prm.q_grid.project(q)
    res = p_next_new - q
    return q, u + rho * res, jnp.sum(res.astype(jnp.float32) ** 2)


def init(key, Xp, n_layers: int, prm: Params, dtype=jnp.float32
         ) -> List[Layer]:
    """Forward-consistent start: W_l ~ N(0, 2/h), z_l = p_l W_l, q_l =
    relu(z_l) (on the p grid in pdADMM-G-Q), p_{l+1} = q_l, b = u = 0."""
    h = Xp.shape[1]
    keys = jax.random.split(key, n_layers)
    cur = Xp.astype(dtype)
    layers = []
    for l in range(n_layers):
        W = (jax.random.normal(keys[l], (h, h), jnp.float32)
             * jnp.sqrt(2.0 / h)).astype(dtype)
        z = _mm(cur, W)
        q = _relu(z)
        if prm.p_grid is not None:
            q = prm.p_grid.project(q)
        layers.append(Layer(cur, W, jnp.zeros((h,), dtype), z, q,
                            jnp.zeros_like(z)))
        cur = q
    return layers


def iterate(layers: List[Layer], labels, mask, prm: Params):
    """One ADMM iteration over all layers -> (new layers, primal residual)."""
    L = len(layers)
    zero = jnp.zeros_like(layers[0].q)
    new = []
    for l, cur in enumerate(layers):
        q_prev = layers[l - 1].q if l else zero
        u_prev = layers[l - 1].u if l else zero
        new.append(primal(cur, q_prev, u_prev, labels, mask, prm=prm,
                          first=l == 0, last=l == L - 1))
    out, res_sq = [], jnp.zeros((), jnp.float32)
    for l, (cur, (p, W, b, z)) in enumerate(zip(layers, new)):
        if l == L - 1:
            q, u = cur.q, cur.u
        else:
            q, u, rs = dual(cur.q, cur.u, z, new[l + 1][0], prm=prm)
            res_sq = res_sq + rs
        out.append(Layer(p, W, b, z, q, u))
    return out, jnp.sqrt(res_sq)

