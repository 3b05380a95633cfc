"""Pallas TPU kernels: pack integer wire codes into their physical uint8
container (and back) — the fused half of the gather-based quantized
all-reduce and of the padded-container boundary exchange.

Layout contract (shared bit-for-bit with the jnp oracle
``repro.comm.codecs.pack_codes_jnp`` / ``unpack_codes_jnp``):

  * ``bits <= 4`` — codes padded to an even length ``n2`` and HALF-SPLIT:
    byte ``i`` carries code ``i`` in its high nibble and code
    ``i + n2/2`` in its low nibble. Both reads are contiguous halves of
    the flat code stream (no strided lane access, which Mosaic dislikes),
    and unpacking is ``concat(hi, lo)[:n]`` — the exact inverse.
  * ``bits <= 8`` — the identity: uint8 codes ARE the container (a copy
    kernel would fuse nothing, so none is emitted).
  * ``bits <= 16`` — big-endian byte planes: all high bytes first, then
    all low bytes (two contiguous writes).

All in-kernel arithmetic runs in int32 (TPU shift semantics on sub-32-bit
integers are not guaranteed across generations) and casts to the container
dtype on the way out. The public helpers view the flat stream as one
``(1, m)`` row — the ops are elementwise, so the tiling is shape-free: a
ragged length takes a ``pl.cdiv`` grid whose edge block Pallas masks.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _pack4_kernel(hi_ref, lo_ref, o_ref):
    hi = hi_ref[...].astype(jnp.int32)
    lo = lo_ref[...].astype(jnp.int32)
    o_ref[...] = ((hi << 4) | (lo & 0xF)).astype(jnp.uint8)


def _unpack4_kernel(b_ref, hi_ref, lo_ref):
    b = b_ref[...].astype(jnp.int32)
    hi_ref[...] = ((b >> 4) & 0xF).astype(jnp.uint8)
    lo_ref[...] = (b & 0xF).astype(jnp.uint8)


def _pack16_kernel(c_ref, hi_ref, lo_ref):
    c = c_ref[...].astype(jnp.int32)
    hi_ref[...] = ((c >> 8) & 0xFF).astype(jnp.uint8)
    lo_ref[...] = (c & 0xFF).astype(jnp.uint8)


def _unpack16_kernel(hi_ref, lo_ref, o_ref):
    hi = hi_ref[...].astype(jnp.int32)
    lo = lo_ref[...].astype(jnp.int32)
    o_ref[...] = ((hi << 8) | lo).astype(jnp.uint16)


def _rowcall(kernel, ins, out_dtypes, *, bn: int = 8192,
             interpret: bool = False):
    """Run an elementwise multi-in/multi-out kernel over flat streams viewed
    as one (1, m) row, tiled (1, bn); the edge block of a ragged length hangs
    past the row and its out-of-bounds writes are masked."""
    m = ins[0].shape[0]
    if m == 0:                         # nothing to move; match the oracle
        return [jnp.zeros((0,), dt) for dt in out_dtypes]
    bn_ = min(bn, m)
    outs = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(m, bn_),),
        in_specs=[pl.BlockSpec((1, bn_), lambda i: (0, i))] * len(ins),
        out_specs=[pl.BlockSpec((1, bn_), lambda i: (0, i))] * len(out_dtypes),
        out_shape=[jax.ShapeDtypeStruct((1, m), dt) for dt in out_dtypes],
        interpret=interpret,
    )(*[x.reshape(1, -1) for x in ins])
    return [o.reshape(-1) for o in outs]


def pack_codes(codes, bits: int, *, interpret: bool = False):
    """Flat integer codes -> uint8 container of exactly
    ``codecs._body_bytes(bits, codes.size)`` bytes."""
    flat = codes.ravel()
    n = flat.shape[0]
    if bits <= 4:
        flat = flat.astype(jnp.uint8)
        if n % 2:
            flat = jnp.concatenate([flat, jnp.zeros((1,), jnp.uint8)])
        h = flat.shape[0] // 2
        (out,) = _rowcall(_pack4_kernel, [flat[:h], flat[h:]], [jnp.uint8],
                          interpret=interpret)
        return out
    if bits <= 8:
        return flat.astype(jnp.uint8)
    hi, lo = _rowcall(_pack16_kernel, [flat.astype(jnp.uint16)],
                      [jnp.uint8, jnp.uint8], interpret=interpret)
    return jnp.concatenate([hi, lo])


def unpack_codes(packed, bits: int, n: int, *, interpret: bool = False):
    """uint8 container -> the first `n` integer codes (container dtype)."""
    if bits <= 4:
        h = (n + 1) // 2
        hi, lo = _rowcall(_unpack4_kernel, [packed[:h]],
                          [jnp.uint8, jnp.uint8], interpret=interpret)
        return jnp.concatenate([hi, lo])[:n]
    if bits <= 8:
        return packed[:n].astype(jnp.uint8)
    (out,) = _rowcall(_unpack16_kernel, [packed[:n], packed[n:2 * n]],
                      [jnp.uint16], interpret=interpret)
    return out
