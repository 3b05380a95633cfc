"""Pallas TPU kernel: the fused backtracking-trial contraction for the
quantized (projected) p-update.

Each trial of the projected backtracking search needs the data-fit term of
φ at the candidate x⁺ = proj(x0 - g/τ):

    ||z - x⁺W - b||² = ||r0 - dW||²,     d = x⁺ - x0,  r0 = z - x0 W - b.

The naive evaluation materializes the [V, n_out] product, writes it to HBM,
re-reads it to subtract from r0, and re-reads the difference to reduce. Here
the d@W tiles accumulate in VMEM, the subtraction and squared reduction ride
the final K step, and only one f32 lane row of column partials per (m, n)
tile ever touches HBM — the trial's HBM traffic drops from O(V·n_out) to
O(V·n_out / bm).

Each tile reduces over its rows only and writes a (1, bn) row into a
[M/bm, 1, N] partials array (a block shape the TPU layout rules accept,
unlike a (1, 1) scalar block); the final ``jnp.sum`` over the partials is
noise next to the contraction itself.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _resnorm_kernel(r0_ref, d_ref, w_ref, out_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(d_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _reduce():
        r = r0_ref[...].astype(jnp.float32) - acc_ref[...]
        out_ref[...] = jnp.sum(r * r, axis=0, keepdims=True)


def backtrack_resnorm(r0, d, W, *, bm: int = 256, bk: int = 512,
                      bn: int = 256, interpret: bool = False):
    """||r0 - d @ W||² as one fused matmul+reduce. r0: [M,N], d: [M,K],
    W: [K,N]. Returns a float32 scalar."""
    M, K = d.shape
    K2, N = W.shape
    assert K == K2 and r0.shape == (M, N), (r0.shape, d.shape, W.shape)
    bm, bk, bn = min(bm, M), min(bk, K), min(bn, N)
    assert M % bm == 0 and K % bk == 0 and N % bn == 0, (d.shape, W.shape)
    n_k = K // bk

    kernel = functools.partial(_resnorm_kernel, n_k=n_k)
    partials = pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),   # r0
            pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),   # d
            pl.BlockSpec((bk, bn), lambda m, n, k: (k, n)),   # W
        ],
        out_specs=pl.BlockSpec((None, 1, bn), lambda m, n, k: (m, 0, n)),
        out_shape=jax.ShapeDtypeStruct((M // bm, 1, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(r0, d, W)
    return jnp.sum(partials)
