"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth the
per-kernel shape/dtype sweeps assert against)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def fused_linear_ref(p, W, b, z=None, *, mode: str = "linear"):
    out = (p.astype(jnp.float32) @ W.astype(jnp.float32)
           + b.astype(jnp.float32))
    if mode == "residual":
        out = z.astype(jnp.float32) - out
    return out.astype(p.dtype)


def admm_pgrad_ref(r, W, u, p, q, *, nu: float, rho: float):
    g = (-nu) * (r.astype(jnp.float32) @ W.astype(jnp.float32).T) \
        + u.astype(jnp.float32) \
        + rho * (p.astype(jnp.float32) - q.astype(jnp.float32))
    return g.astype(p.dtype)


def backtrack_resnorm_ref(r0, d, W):
    r = r0.astype(jnp.float32) - d.astype(jnp.float32) @ W.astype(jnp.float32)
    return jnp.sum(r * r)


def grid_project_ref(x, grid):
    return grid.project(x)


def fista_zlast_ref(a, z_old, labels, label_mask, *, nu: float,
                    n_iters: int = 15, n_classes=None):
    """jnp oracle for the fused FISTA z_L kernel: the shared
    `subproblems.fista_ce` loop (masked CE over the first `n_classes`
    columns + proximal term, Nesterov momentum)."""
    from repro.core.subproblems import fista_ce
    return fista_ce(a, z_old, labels, label_mask, nu, n_iters, n_classes)


def pack_codes_ref(codes, bits: int):
    """jnp oracle for the wire-container pack kernel: the canonical layout
    lives in `repro.comm.codecs.pack_codes_jnp` (half-split nibbles /
    identity bytes / big-endian byte planes)."""
    from repro.comm.codecs import pack_codes_jnp
    return pack_codes_jnp(codes, bits)


def unpack_codes_ref(packed, bits: int, n: int):
    from repro.comm.codecs import unpack_codes_jnp
    return unpack_codes_jnp(packed, bits, n)


def relu_zupdate_ref(a, q, z_old):
    from repro.core.subproblems import update_z_hidden
    return update_z_hidden(a.astype(jnp.float32), q.astype(jnp.float32),
                           z_old.astype(jnp.float32), 1.0).astype(a.dtype)


def flash_attention_ref(q, k, v, *, causal: bool = True):
    """q: [B,H,S,D]; k,v: [B,H,T,D]."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (q.shape[-1] ** -0.5)
    if causal:
        Sq, T = q.shape[2], k.shape[2]
        mask = jnp.arange(Sq)[:, None] >= jnp.arange(T)[None, :]
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)
                      ).astype(q.dtype)
