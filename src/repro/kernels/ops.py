"""Dispatch layer for the Pallas kernels — the ONE place that decides how a
hot op executes.

Dispatch policy (resolved per call, outside the jit boundary):

  1. ``use_pallas=None`` (the default every hot-loop caller should use)
     follows the ``REPRO_KERNELS`` environment variable:

       * ``auto`` (default) — compiled Pallas on a TPU backend; the pure-jnp
         ``ref`` oracles everywhere else. CPU interpret mode is NEVER
         auto-selected: it exists for kernel correctness, and is orders of
         magnitude slower than letting XLA fuse the jnp expression.
       * ``ref`` — force the jnp oracles (useful for A/B numerics).
       * ``pallas`` — force compiled Pallas (TPU runtimes).
       * ``interpret`` — force Pallas in interpret mode (CI's interpret legs
         run the whole fast path this way so the kernel wiring is exercised
         on every PR without TPU hardware).

  2. Explicit ``use_pallas=True/False`` overrides the policy; with
     ``use_pallas=True``, ``interpret=None`` resolves to interpret mode on
     any non-TPU backend. An explicit ``interpret=`` with ``use_pallas``
     left as None implies the Pallas path (``interpret=False`` = compiled) —
     asking for an interpretation mode IS asking for the kernel.

  3. Pad-to-tile: the matmul kernels want 128-ish tile divisibility. When a
     Pallas path is selected and the operand shapes cannot tile, dispatch
     zero-pads each dimension up to the kernel's tile (``padded_shape``
     gives the exact plan per op), runs the kernel, and slices the true
     shape back out — so ragged real-graph sizes (V = 2485, 2708, 3327,
     ...) take the fused kernel instead of silently falling back to
     ``ref``. Zero padding is exact for every op here: padded rows/columns
     contribute nothing to contractions, and padded outputs are sliced off
     (``backtrack_resnorm``'s scalar is untouched because every padded term
     is 0 − 0). The padding happens INSIDE the jit'd dispatch body, so
     pad/slice fuse around the kernel call.

The policy is re-read on every call (cheap), but note each resolved variant
is a separate jit specialization; flipping ``REPRO_KERNELS`` mid-process
never reuses a stale compilation.

``fista_zlast`` is the fused z_L solve (Eq. 7): one Pallas dispatch per
FISTA iteration (log-softmax + masked CE gradient + proximal term + momentum
in-register), with the jnp loop ``ref.fista_zlast_ref`` as its oracle.

``pack_codes``/``unpack_codes`` format integer wire codes into their
physical uint8 container (half-split nibbles for int4, byte planes for
int16; the layout contract is ``comm.codecs.pack_codes_jnp``). They are the
fused half of the gather-based packed all-reduce and the padded-container
boundary exchange in ``comm/transport.py`` — the former "packed-int4 psum"
kernel gap. Packing is elementwise, so there is no tile-divisibility guard:
ragged streams (like ragged ``relu_zupdate`` shapes) take a ``pl.cdiv`` grid
whose edge block Pallas masks — no padding copy.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.kernels import (admm_pgrad as _pg, backtrack_phi as _bt,
                           fista_zlast as _fz, flash_attention as _fa,
                           fused_linear as _fl, pack_codes as _pk,
                           quantize_kernel as _qk, ref, relu_zupdate as _zu)

POLICY_ENV = "REPRO_KERNELS"

# Pallas kernel-body names as they appear in `pallas_call` eqn params
# (`name_and_src_info.name`) — the introspection surface the program-
# contract linter (`repro.analysis.contracts`) keys its per-kernel
# dispatch counts on. vmap'd dispatches get a `_batched` suffix (the
# layer-stacked fast path wraps every stacked op in vmap).
KERNEL_NAMES = {
    "fused_linear": "_matmul_kernel",
    "admm_pgrad": "kernel",            # nested def inside admm_pgrad
    "backtrack_resnorm": "_resnorm_kernel",
    "fista_zlast": "_fista_step_kernel",
    "relu_zupdate": "_zupdate_kernel",
    "flash_attention": "_flash_kernel",
    "grid_project": "_project_kernel",
}


def pack_kernel_names(bits: int):
    """(pack, unpack) kernel-body names for a `bits`-wide wire container, or
    ``None`` for widths whose packing is the identity (4 < bits <= 8: the
    uint8 codes ARE the container, so no kernel is dispatched)."""
    if bits <= 4:
        return "_pack4_kernel", "_unpack4_kernel"
    if bits <= 8:
        return None
    return "_pack16_kernel", "_unpack16_kernel"


def dispatch_policy() -> str:
    """The ``REPRO_KERNELS`` policy in force right now (normalized)."""
    policy = os.environ.get(POLICY_ENV, "auto")
    return policy if policy in ("auto", "ref", "pallas", "interpret") \
        else "auto"


def kernels_enabled() -> bool:
    """True iff a bare dispatch (``use_pallas=None``) routes to a Pallas
    kernel under the current policy/backend — i.e. whether `pallas_call`
    eqns should appear in a freshly traced program at all."""
    return _resolve(None, None)[0]


def _resolve(use_pallas, interpret):
    """-> (use_pallas: bool, interpret: bool), per the module policy."""
    on_tpu = jax.default_backend() == "tpu"
    if use_pallas is None:
        if interpret is not None:
            # an explicit interpret request implies the Pallas path
            # (interpret=False means compiled Pallas)
            return True, interpret
        policy = os.environ.get(POLICY_ENV, "auto")
        if policy == "ref":
            return False, False
        if policy == "pallas":
            return True, False
        if policy == "interpret":
            return True, True
        return (True, False) if on_tpu else (False, False)
    if not use_pallas:
        return False, False
    return True, (not on_tpu) if interpret is None else interpret


# ---------------------------------------------------------------------------
# Pad-to-tile plans. Per dimension: (block, align) — a dimension n pads up to
# a multiple of `block` when n >= block (so the kernel's min(block, n) tile
# divides it), else up to a multiple of `align` (the TPU sublane/lane
# granularity, and then the whole dimension IS the tile).
# ---------------------------------------------------------------------------

PAD_BLOCKS = {
    "fused_linear": ((256, 8), (512, 128), (256, 128)),       # (M, K, N)
    "admm_pgrad": ((256, 8), (256, 128), (256, 128)),         # (V, n_out, n_in)
    "backtrack_resnorm": ((256, 8), (512, 128), (256, 128)),  # (M, K, N)
    "fista_zlast": ((256, 8), (128, 128)),                    # (V, width)
}


def _pad_dim(n: int, block: int, align: int) -> int:
    if n >= block:
        return -(-n // block) * block
    return -(-n // align) * align


def padded_shape(op: str, dims) -> tuple:
    """The logical shape the dispatch layer pads `dims` up to before calling
    the `op` kernel (identity when the dims already tile). Introspection
    surface for the pad-to-tile regression tests."""
    return tuple(_pad_dim(n, blk, al)
                 for n, (blk, al) in zip(dims, PAD_BLOCKS[op]))


def _pad2(x, rows: int, cols: int):
    r, c = x.shape
    if (r, c) == (rows, cols):
        return x
    return jnp.pad(x, ((0, rows - r), (0, cols - c)))


# ---------------------------------------------------------------------------
# jit'd implementations (static dispatch flags resolved by the wrappers)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("mode", "use_pallas", "interpret"))
def _fused_linear(p, W, b, z, *, mode, use_pallas, interpret):
    if not use_pallas:
        return ref.fused_linear_ref(p, W, b, z, mode=mode)
    (M, K), N = p.shape, W.shape[1]
    Mp, Kp, Np = padded_shape("fused_linear", (M, K, N))
    out = _fl.fused_linear(
        _pad2(p, Mp, Kp), _pad2(W, Kp, Np), jnp.pad(b, (0, Np - N)),
        None if z is None else _pad2(z, Mp, Np),
        mode=mode, interpret=interpret)
    return out[:M, :N]


def fused_linear(p, W, b, z=None, *, mode="linear", use_pallas=None,
                 interpret=None):
    up, it = _resolve(use_pallas, interpret)
    return _fused_linear(p, W, b, z, mode=mode, use_pallas=up, interpret=it)


@functools.partial(jax.jit, static_argnames=("nu", "rho", "use_pallas",
                                             "interpret"))
def _admm_pgrad(r, W, u, p, q, *, nu, rho, use_pallas, interpret):
    if not use_pallas:
        return ref.admm_pgrad_ref(r, W, u, p, q, nu=nu, rho=rho)
    (V, n_out), n_in = r.shape, W.shape[0]
    Vp, kp, np_ = padded_shape("admm_pgrad", (V, n_out, n_in))
    out = _pg.admm_pgrad(
        _pad2(r, Vp, kp), _pad2(W, np_, kp), _pad2(u, Vp, np_),
        _pad2(p, Vp, np_), _pad2(q, Vp, np_),
        nu=nu, rho=rho, interpret=interpret)
    return out[:V, :n_in]


def admm_pgrad(r, W, u, p, q, *, nu, rho, use_pallas=None, interpret=None):
    up, it = _resolve(use_pallas, interpret)
    return _admm_pgrad(r, W, u, p, q, nu=nu, rho=rho, use_pallas=up,
                       interpret=it)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def _backtrack_resnorm(r0, d, W, *, use_pallas, interpret):
    if not use_pallas:
        return ref.backtrack_resnorm_ref(r0, d, W)
    (M, K), N = d.shape, W.shape[1]
    Mp, Kp, Np = padded_shape("backtrack_resnorm", (M, K, N))
    # zero padding adds only (0 - 0)² terms, so the scalar is exact
    return _bt.backtrack_resnorm(_pad2(r0, Mp, Np), _pad2(d, Mp, Kp),
                                 _pad2(W, Kp, Np), interpret=interpret)


def backtrack_resnorm(r0, d, W, *, use_pallas=None, interpret=None):
    """||r0 - d @ W||² (the projected backtracking trial's data-fit term)."""
    up, it = _resolve(use_pallas, interpret)
    return _backtrack_resnorm(r0, d, W, use_pallas=up, interpret=it)


@functools.partial(jax.jit, static_argnames=("nu", "n_iters", "n_classes",
                                             "use_pallas", "interpret"))
def _fista_zlast(a, z_old, labels, label_mask, *, nu, n_iters, n_classes,
                 use_pallas, interpret):
    if not use_pallas:
        return ref.fista_zlast_ref(a, z_old, labels, label_mask, nu=nu,
                                   n_iters=n_iters, n_classes=n_classes)
    V, N = a.shape
    C = N if n_classes is None else n_classes
    Vp, Np = padded_shape("fista_zlast", (V, N))
    # padded rows carry mask 0 (CE grad vanishes) and a = z = 0 (the prox
    # flow keeps them at 0); padded columns sit outside n_classes
    out = _fz.fista_zlast(
        _pad2(a, Vp, Np), _pad2(z_old, Vp, Np),
        jnp.pad(labels, (0, Vp - V)), jnp.pad(label_mask, (0, Vp - V)),
        nu=nu, n_iters=n_iters, n_classes=C, interpret=interpret)
    return out[:V, :N]


def fista_zlast(a, z_old, labels, label_mask, *, nu, n_iters=15,
                n_classes=None, use_pallas=None, interpret=None):
    """Fused FISTA z_L solve (Eq. 7): min_z R(z;y) + (ν/2)||z − a||², R the
    masked CE over z[:, :n_classes] (default: the full width). One Pallas
    dispatch per FISTA iteration; `ref.fista_zlast_ref` on the jnp path."""
    up, it = _resolve(use_pallas, interpret)
    return _fista_zlast(a, z_old, labels, label_mask, nu=float(nu),
                        n_iters=int(n_iters),
                        n_classes=None if n_classes is None else int(n_classes),
                        use_pallas=up, interpret=it)


@functools.partial(jax.jit, static_argnames=("bits", "use_pallas",
                                             "interpret"))
def _pack_codes(codes, *, bits, use_pallas, interpret):
    if not use_pallas:
        return ref.pack_codes_ref(codes, bits)
    return _pk.pack_codes(codes, bits, interpret=interpret)


def pack_codes(codes, bits, *, use_pallas=None, interpret=None):
    """Pack flat integer wire codes to their physical width: a uint8
    container of exactly ``codecs._body_bytes(bits, codes.size)`` bytes
    (int4 half-split nibbles / int8 identity / int16 byte planes)."""
    up, it = _resolve(use_pallas, interpret)
    return _pack_codes(codes, bits=int(bits), use_pallas=up, interpret=it)


@functools.partial(jax.jit, static_argnames=("bits", "n", "use_pallas",
                                             "interpret"))
def _unpack_codes(packed, *, bits, n, use_pallas, interpret):
    if not use_pallas:
        return ref.unpack_codes_ref(packed, bits, n)
    return _pk.unpack_codes(packed, bits, n, interpret=interpret)


def unpack_codes(packed, bits, n, *, use_pallas=None, interpret=None):
    """Inverse of :func:`pack_codes`: the first `n` codes in the container
    dtype (uint8 for <= 8 bits, uint16 above)."""
    up, it = _resolve(use_pallas, interpret)
    return _unpack_codes(packed, bits=int(bits), n=int(n), use_pallas=up,
                         interpret=it)


def grid_project(x, grid, *, use_pallas=None, interpret=None):
    up, it = _resolve(use_pallas, interpret)
    if not up:
        return ref.grid_project_ref(x, grid)
    return _qk.grid_project(x, grid, interpret=it)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def _relu_zupdate(a, q, z_old, *, use_pallas, interpret):
    if not use_pallas:
        return ref.relu_zupdate_ref(a, q, z_old)
    return _zu.relu_zupdate(a, q, z_old, interpret=interpret)


def relu_zupdate(a, q, z_old, *, use_pallas=None, interpret=None):
    """Fused Eq.-6 ReLU z-update. Accepts [..., V, n]: leading axes (the
    layer-stacked fast path) ride a grid axis of their own — the op is
    elementwise, so the tiling is shape-free."""
    up, it = _resolve(use_pallas, interpret)
    return _relu_zupdate(a, q, z_old, use_pallas=up, interpret=it)


@functools.partial(jax.jit, static_argnames=("causal", "use_pallas",
                                             "interpret"))
def _flash_attention(q, k, v, *, causal, use_pallas, interpret):
    if not use_pallas:
        return ref.flash_attention_ref(q, k, v, causal=causal)
    return _fa.flash_attention(q, k, v, causal=causal, interpret=interpret)


def flash_attention(q, k, v, *, causal=True, use_pallas=None, interpret=None):
    up, it = _resolve(use_pallas, interpret)
    return _flash_attention(q, k, v, causal=causal, use_pallas=up,
                            interpret=it)
