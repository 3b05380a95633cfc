"""Pallas TPU kernel: one fused FISTA step of the z_L solve (Eq. 7).

The last-layer z-subproblem  min_z R(z; y) + (ν/2)||z − a||²  (R = masked
softmax cross-entropy) is solved by FISTA. The naive loop body issues a
separate dispatch chain per iteration — log-softmax (max, sub, exp, sum),
CE gradient (softmax − one-hot, mask), the proximal term ν(y − a) and the
momentum extrapolation each round-trip a [V, C] tensor through HBM. Here the
whole body is ONE kernel: row-tiled over V with the entire class dimension
in-register, so per iteration each of (z_prev, z_cur, a) is read once and
z_next written once — 4 HBM tensor touches instead of ~12.

The FISTA momentum sequence t_{k+1} = (1 + √(1+4t_k²))/2 is data-INDEPENDENT,
so the per-iteration extrapolation weight (t_k − 1)/t_{k+1} is precomputed
host-side (`momentum_schedule`) and baked into each dispatch as a static
scalar: the kernel needs no scalar prefetch and no carried t.

Columns ≥ `n_classes` (tile padding, or a caller's columns past the
logits) are excluded from the softmax/CE terms but still follow the
proximal flow — exactly the padded-gradient semantics of `stage_parallel`'s
risk. That flow has data-independent coefficients: each step maps
y ↦ s·y + (1 − s)·a, s = 1/(1+ν), so such a column ends the solve at
α·z_old + (1 − α)·a, α = `prox_carry_weight(n_iters, nu)`. The head-folded
layout, where only z[:, :C] of h columns carries logits, therefore hands
this kernel its C logit columns alone and writes the rest in closed form
(`subproblems.update_z_last`).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def momentum_schedule(n_iters: int) -> list:
    """Extrapolation weights for the initial gradient step plus `n_iters`
    FISTA iterations: [0, (t_1−1)/t_2, ...], t_1 = 1. Python floats (exact
    f64), data-independent, so they compile as constants."""
    ms = [0.0]
    t = 1.0
    for _ in range(n_iters):
        t_new = (1.0 + math.sqrt(1.0 + 4.0 * t * t)) / 2.0
        ms.append((t - 1.0) / t_new)
        t = t_new
    return ms


def prox_carry_weight(n_iters: int, nu: float) -> float:
    """z_old's weight α in the result of the solve on a column outside the
    softmax, where the gradient is ν(y − a): z = α·z_old + (1 − α)·a.
    Each step keeps the iterate an affine mix of z_old and a whose weights
    sum to 1, so the scalar weight of z_old runs through the same momentum
    schedule and step 1/(1+ν) as the kernel. Python floats: a constant."""
    step = 1.0 / (1.0 + nu)
    prev = cur = 1.0                   # z_prev = z_cur = z_old at the start
    for mom in momentum_schedule(n_iters):
        prev, cur = cur, step * (cur + mom * (cur - prev))
    return cur


def _fista_step_kernel(zp_ref, zc_ref, a_ref, lab_ref, mask_ref, out_ref, *,
                       mom: float, step: float, nu: float, n_classes: int):
    dt = jnp.promote_types(out_ref.dtype, jnp.float32)
    zp = zp_ref[...].astype(dt)
    zc = zc_ref[...].astype(dt)
    a = a_ref[...].astype(dt)

    y = zc + mom * (zc - zp)

    cols = jax.lax.broadcasted_iota(jnp.int32, y.shape, 1)
    cmask = cols < n_classes
    logits = jnp.where(cmask, y, -jnp.inf)
    m = jnp.max(logits, axis=1, keepdims=True)
    e = jnp.where(cmask, jnp.exp(y - m), 0.0)
    p = e / jnp.sum(e, axis=1, keepdims=True)

    onehot = (cols == lab_ref[...]).astype(dt)          # lab: [bm, 1] int32
    g = (p - onehot) * mask_ref[...].astype(dt) + nu * (y - a)
    out_ref[...] = (y - step * g).astype(out_ref.dtype)


def fista_step(z_prev, z_cur, a, labels2, mask2, *, mom: float, step: float,
               nu: float, n_classes: int, bm: int = 256,
               interpret: bool = False):
    """One fused FISTA iteration: y = z_cur + mom·(z_cur − z_prev), then
    z_next = y − step·(∇R(y) + ν(y − a)). labels2/mask2 are column vectors
    [V, 1] (int32 / float)."""
    V, N = a.shape
    bm = min(bm, V)
    assert V % bm == 0, (a.shape, bm)
    kernel = functools.partial(_fista_step_kernel, mom=mom, step=step,
                               nu=nu, n_classes=n_classes)
    return pl.pallas_call(
        kernel,
        grid=(V // bm,),
        in_specs=[pl.BlockSpec((bm, N), lambda i: (i, 0))] * 3
        + [pl.BlockSpec((bm, 1), lambda i: (i, 0))] * 2,
        out_specs=pl.BlockSpec((bm, N), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((V, N), a.dtype),
        interpret=interpret,
    )(z_prev, z_cur, a, labels2, mask2)


def fista_zlast(a, z_old, labels, label_mask, *, nu: float, n_iters: int,
                n_classes: int, bm: int = 256, interpret: bool = False):
    """The full z_L solve: `n_iters + 1` fused dispatches (the initial
    gradient step plus one per FISTA iteration), same iteration map as the
    jnp oracle `ref.fista_zlast_ref`."""
    V, N = a.shape
    labels2 = labels.reshape(V, 1).astype(jnp.int32)
    mask2 = label_mask.reshape(V, 1)
    step = 1.0 / (1.0 + nu)
    moms = momentum_schedule(n_iters)

    run = functools.partial(fista_step, a=a, labels2=labels2, mask2=mask2,
                            step=step, nu=nu, n_classes=n_classes, bm=bm,
                            interpret=interpret)
    z_prev, z_cur = z_old, run(z_old, z_old, mom=moms[0])
    for mom in moms[1:]:
        z_prev, z_cur = z_cur, run(z_prev, z_cur, mom=mom)
    return z_cur
