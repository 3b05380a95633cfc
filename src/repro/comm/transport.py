"""Transport entry points: every collective whose payload crosses a link.

``parallel/stage_parallel.py`` (neighbor ppermute shifts) and
``parallel/collectives.py`` (quantized all-reduce) call these instead of
hand-rolling encode/decode. All functions are pure and trace-safe — byte
accounting happens OUTSIDE jit via the `wire_bytes`/`psum_wire_bytes`
helpers, which the runtimes feed to a :class:`~repro.comm.ledger.CommLedger`
using the same static shapes the traced program saw.

Shared-scale all-reduce model: a scalar min/max handshake fixes ONE affine
grid across shards, the integer codes are summed exactly in int32, and the
only lossy step is each shard's rounding (unbiased under stochastic
rounding). Two PHYSICAL collectives realize that model:

  * ``code_psum`` — ``jax.lax.psum`` of the int32 codes. Exact, but the
    message each shard injects is the int32 container: 4 B/element on the
    wire regardless of the codec.
  * ``gather`` — each shard packs its codes to their physical width
    (int4 half-split nibbles / int8 / int16 byte planes in a uint8
    container, fused via ``ops.pack_codes``), ``all_gather``s the packed
    payloads, and decodes + sums the int32 codes locally. The shared-scale
    handshake replaces any per-shard header, so the injected message is
    exactly the packed container. Integer addition is exact and the final
    affine decode is the same expression, so both collectives are
    bit-identical in value.

Cost model (:func:`psum_mode`): under a ring schedule, the gather moves each
shard's packed payload across ``world - 1`` links (total fabric bytes
``world * (world-1) * n * bits/8``) while the int32 code-psum moves
``~ 8 * n * (world-1)`` in its reduce-scatter + all-gather halves — so the
gather wins exactly when ``world * bits < 64`` and ``quantized_psum``
selects it then, falling back to ``code_psum`` for wide codecs / large
worlds. The ledger charges each shard's *injected* message at its physical
container width (`wire_bytes`; the ring replication factors are algorithm
details, like the in-flight accumulator of a psum) next to the codec's
logical `payload_bytes`.

Padded wire containers (:class:`PaddedWire` / :class:`ContainerExchange`):
the SPMD boundary exchange compiles ONE wire format per step, so per-edge
bit-widths historically meant per-schedule recompiles. A ``PaddedWire``
fixes the physical format instead: every slab ships as a flat uint8
container sized for the WIDEST allowed codec (`capacity`), the active
bit-width is a traced per-stage index into the static ``widths`` table, and
encode/decode branch with ``lax.switch`` — so one compiled step serves
every per-boundary, per-iteration schedule the controller emits. Physical
bytes on the link are the container capacity (charged as `wire_bytes`); the
active codec's packed size is the logical `payload_bytes` the schedule
saves.

Wire integrity (fault tolerance): :mod:`repro.comm.faults` wraps these
exchanges with a checksum/seqno header (int32[2] ppermuted next to the
payload, +8 physical wire bytes per slab per link, kind ``"header"`` on
the ledger) and a deterministic fault injector —
:class:`~repro.comm.faults.SentinelExchange` composes the codec /
container formats defined here rather than re-implementing them, and the
same :func:`~repro.comm.faults.payload_checksum` verifies packed
``quantized_psum`` gather payloads. The header format and the
``metrics["health"]`` schema are documented in that module's docstring.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.comm.codecs import (FP32, AffineCodec, Fp32Codec, GridCodec,
                               WireCodec, WirePayload, _body_bytes,
                               _container_dtype, _n_elements)
from repro.kernels import ops


# ---------------------------------------------------------------------------
# Neighbor exchange (pipeline/stage ring)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class NeighborExchange:
    """Codec-formatted boundary exchange over a ring axis.

    The payload is the boundary slab only (one layer of the local stack);
    interior layers move by a local roll, exactly as in the paper's
    layer-client pipeline.

    Every shift comes in two halves so the runtime can hide the message
    latency behind independent compute (double-buffered overlap):

      * ``start_shift_*``  — encode the boundary slab and ISSUE the
        ``ppermute``; returns the in-flight :class:`WirePayload` (a carryable
        pytree — e.g. through a ``lax.scan`` carry across iterations),
      * ``finish_shift_*`` — decode the arrived payload and concatenate it
        with the locally-rolled interior layers.

    ``shift_from_prev``/``shift_from_next`` are exactly
    ``finish(start(x), x)`` — the eager composition — so split and fused
    call sites are bitwise-identical by construction.
    """

    axis_name: str
    codec: WireCodec = FP32

    def _start(self, boundary, perm) -> WirePayload:
        payload = self.codec.encode(boundary)
        return jax.tree.map(
            lambda t: jax.lax.ppermute(t, self.axis_name, perm), payload)

    # -- forward shift (out[i] = x[i-1]) ------------------------------------
    def start_shift_from_prev(self, x_loc) -> WirePayload:
        """Encode x_loc[-1:] and issue the forward boundary ppermute; the
        returned in-flight payload is consumed by `finish_shift_from_prev`
        (possibly next iteration, with the same x_loc values)."""
        n = jax.lax.axis_size(self.axis_name)
        perm = [(i, (i + 1) % n) for i in range(n)]
        return self._start(x_loc[-1:], perm)

    def finish_shift_from_prev(self, payload: WirePayload, x_loc):
        """Decode an in-flight forward payload and splice it in: out[i] =
        x[i-1], out[0] fetched from the previous stage (garbage into global
        layer 0 — masked by the caller)."""
        boundary = self.codec.decode(payload, shape=x_loc[-1:].shape,
                                     dtype=x_loc.dtype)
        return jnp.concatenate([boundary, x_loc[:-1]], axis=0)

    def shift_from_prev(self, x_loc):
        """out[i] = x[i-1]; out[0] fetched from the previous stage (garbage
        into global layer 0 — masked by the caller)."""
        return self.finish_shift_from_prev(self.start_shift_from_prev(x_loc),
                                           x_loc)

    # -- backward shift (out[i] = x[i+1]) -----------------------------------
    def start_shift_from_next(self, x_loc) -> WirePayload:
        """Encode x_loc[:1] and issue the backward boundary ppermute."""
        n = jax.lax.axis_size(self.axis_name)
        perm = [(i, (i - 1) % n) for i in range(n)]
        return self._start(x_loc[:1], perm)

    def finish_shift_from_next(self, payload: WirePayload, x_loc):
        """Decode an in-flight backward payload and splice it in: out[i] =
        x[i+1], out[-1] fetched from the next stage (garbage into global
        layer L-1 — masked by the caller)."""
        boundary = self.codec.decode(payload, shape=x_loc[:1].shape,
                                     dtype=x_loc.dtype)
        return jnp.concatenate([x_loc[1:], boundary], axis=0)

    def shift_from_next(self, x_loc):
        """out[i] = x[i+1]; out[-1] fetched from the next stage (garbage into
        global layer L-1 — masked by the caller)."""
        return self.finish_shift_from_next(self.start_shift_from_next(x_loc),
                                           x_loc)

    def wire_bytes(self, boundary_shape) -> int:
        """Exact bytes one shift puts on one link."""
        return self.codec.payload_bytes(boundary_shape)


# ---------------------------------------------------------------------------
# Quantized all-reduce (data-parallel axis)
# ---------------------------------------------------------------------------

def _shared_affine(x, axis_name: str, codec: AffineCodec):
    """Scalar min/max handshake -> one affine grid for every shard."""
    lo = jax.lax.pmin(jnp.min(x), axis_name)
    hi = jax.lax.pmax(jnp.max(x), axis_name)
    scale = jnp.maximum((hi - lo) / (2 ** codec.bits - 1), 1e-12)
    return lo, scale


def _grid_codes(grid, x, key):
    """Integer codes on a static grid; stochastic rounding iff `key` given
    (the subsystem-wide rule, same as AffineCodec.quantize)."""
    q = (x - grid.lo) / grid.step
    if key is not None:
        q = jnp.floor(q + jax.random.uniform(key, q.shape))
    else:
        q = jnp.round(q)
    return jnp.clip(q, 0, grid.n_levels - 1)


def _shared_codes(x, axis_name, codec, key):
    """Integer codes against the grid EVERY shard shares: (codes, zero,
    scale). Static for GridCodec; min/max handshake for AffineCodec."""
    if isinstance(codec, GridCodec):
        g = codec.grid
        return _grid_codes(g, x, key), g.lo, g.step
    lo, scale = _shared_affine(x, axis_name, codec)
    return codec.quantize(x, lo, scale, key=key), lo, scale


def _code_psum(codes, zero, scale, axis_name):
    """Exact int32 code-sum; decode is ``scale * code_sum + n * zero``."""
    n = jax.lax.psum(1, axis_name)
    code_sum = jax.lax.psum(codes.astype(jnp.int32), axis_name)
    return code_sum.astype(jnp.float32) * scale + n * zero


GATHER_BREAK_EVEN = 64   # gather wins iff world_size * codec.bits < this

PSUM_MODES = ("psum", "gather", "code_psum")


def _check_mode(mode: Optional[str]) -> Optional[str]:
    if mode is not None and mode not in PSUM_MODES:
        raise ValueError(f"unknown psum mode {mode!r}; expected one of "
                         f"{PSUM_MODES} or None (cost-model selection)")
    return mode


def psum_mode(codec: WireCodec, world_size: int) -> str:
    """The physical collective the cost model selects for a compressed psum:
    ``"psum"`` (plain fp32), ``"gather"`` (packed all-gather + local
    decode-sum) or ``"code_psum"`` (int32 code psum). Ring-model break-even
    — see the module docstring: gather fabric bytes ``w*(w-1)*n*bits/8`` vs
    code-psum ``8*n*(w-1)``, i.e. gather wins iff ``w * bits < 64``.

    This byte rule is the documented fallback of
    :func:`repro.analysis.replay.choose_psum_mode`, which prices the same
    realizations through the measured link model (latency, tolls, local
    encode/decode passes) when a calibrated cost table is available — in
    the bandwidth-dominated limit the two agree."""
    if isinstance(codec, Fp32Codec) or codec.bits >= 32:
        return "psum"
    w = int(world_size)
    return "gather" if w * codec.bits < GATHER_BREAK_EVEN else "code_psum"


def _packed_code_sum(codes, axis_name: str, bits: int, world: int):
    """Pack int codes to their physical width, all_gather the uint8/uint16
    containers, unpack + sum in int32 locally. Exact, like the code psum."""
    icodes = codes.astype(_container_dtype(bits))
    n = icodes.size
    packed = ops.pack_codes(icodes.ravel(), bits)
    arrived = jax.lax.all_gather(packed, axis_name)      # [world, body_bytes]
    total = jnp.zeros((n,), jnp.int32)
    for i in range(world):                               # world is static
        total = total + ops.unpack_codes(arrived[i], bits, n) \
            .astype(jnp.int32)
    return total.reshape(codes.shape)


def _gather_psum(codes, zero, scale, axis_name: str, bits: int, world: int):
    code_sum = _packed_code_sum(codes, axis_name, bits, world)
    return code_sum.astype(jnp.float32) * scale + world * zero


def quantized_psum(x, axis_name: str, codec: WireCodec = AffineCodec(8), *,
                   key: Optional[jax.Array] = None,
                   mode: Optional[str] = None):
    """psum(x) with the payload formatted by `codec`.

    The integer code-sum is exact in int32, so both physical collectives
    (`mode="gather"`: packed all-gather + local decode-sum, the narrow-codec
    path that actually ships `codec.bits` per element; `mode="code_psum"`:
    int32 code psum, the wide-codec/large-world fallback) return
    bit-identical values — ``mode=None`` lets :func:`psum_mode` choose.
    ``mode="psum"`` (or an fp32 codec) is the explicit uncompressed psum.
    Rounding is unbiased stochastic iff `key` is supplied.
    """
    if _check_mode(mode) == "psum" or isinstance(codec, Fp32Codec):
        return jax.lax.psum(x, axis_name)
    w = jax.lax.axis_size(axis_name)
    if mode is None:
        mode = psum_mode(codec, w)
    codes, zero, scale = _shared_codes(x, axis_name, codec, key)
    if mode == "gather":
        return _gather_psum(codes, zero, scale, axis_name, codec.bits, w)
    return _code_psum(codes, zero, scale, axis_name)


def psum_with_error_feedback(x, err, axis_name: str,
                             codec: WireCodec = AffineCodec(8), *,
                             key: Optional[jax.Array] = None,
                             mode: Optional[str] = None
                             ) -> Tuple[jax.Array, jax.Array]:
    """Compressed psum of (x + carried error); returns (summed, new_error).

    new_error = target - what this shard actually transmitted (exact, since
    the grid is shared): cumulative bias stays bounded by one round's error.
    On the gather path the residual is computed against the DECODED PACKED
    codes — the values receivers reconstruct from the wire container — so
    error feedback stays unbiased with respect to the physical payload, not
    the pre-pack codes.
    """
    target = x + err
    if _check_mode(mode) == "psum" or isinstance(codec, Fp32Codec):
        return jax.lax.psum(target, axis_name), jnp.zeros_like(target)
    w = jax.lax.axis_size(axis_name)
    if mode is None:
        mode = psum_mode(codec, w)
    codes, zero, scale = _shared_codes(target, axis_name, codec, key)
    if mode == "gather":
        icodes = codes.astype(_container_dtype(codec.bits))
        packed = ops.pack_codes(icodes.ravel(), codec.bits)
        own = ops.unpack_codes(packed, codec.bits, icodes.size) \
            .astype(jnp.float32).reshape(codes.shape)
        sent = own * scale + zero
        summed = _gather_psum(codes, zero, scale, axis_name, codec.bits, w)
        return summed, target - sent
    sent = codes * scale + zero
    return _code_psum(codes, zero, scale, axis_name), target - sent


@dataclasses.dataclass(frozen=True)
class PsumWireCost:
    """Exact per-shard accounting of one compressed psum: the physical bytes
    of the message this shard injects into the selected collective
    (`wire_bytes`), the codec's logical body bytes (`logical_bytes`, no
    header — the shared handshake replaces it), and the scalar min/max
    handshake (`handshake_bytes`, affine codecs only)."""
    mode: str
    wire_bytes: int
    logical_bytes: int
    handshake_bytes: int


def psum_wire_bytes(codec: WireCodec, shape, world_size: int,
                    mode: Optional[str] = None) -> PsumWireCost:
    """Physical + logical bytes one shard contributes to one compressed psum
    of `shape` at `world_size`, for the collective the cost model selects
    (or an explicit `mode` override). The code-psum path physically ships
    the int32 code container (4 B/element) whatever the codec says; the
    gather path ships the packed container, which IS the codec body."""
    n = _n_elements(shape)
    if _check_mode(mode) is None:
        mode = psum_mode(codec, world_size)
    if mode == "psum":
        return PsumWireCost("psum", 4 * n, 4 * n, 0)
    logical = codec.payload_bytes(shape) - codec.header_bytes()
    handshake = 8 if isinstance(codec, AffineCodec) else 0
    wire = _body_bytes(codec.bits, n) if mode == "gather" else 4 * n
    return PsumWireCost(mode, wire, logical, handshake)


# ---------------------------------------------------------------------------
# Padded wire containers (per-boundary mixed bit-widths in ONE compiled step)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PaddedWire:
    """Fixed-size uint8 wire container over a static table of grid codecs.

    The physical format — a flat uint8 container of :meth:`capacity` bytes,
    sized for the WIDEST width in `widths` — is compile-time constant, so an
    SPMD step using it never respecializes when the schedule changes. The
    ACTIVE width is `sel`, a traced int32 index into `widths`: encode packs
    the active grid's codes (``ops.pack_codes``, the fused kernel path)
    into the head of the container and zero-pads the tail; decode slices
    the active packed length back out. Branching is one ``lax.switch`` over
    the (small, static) width table.
    """

    widths: Tuple[int, ...]              # ascending, e.g. (4, 8, 16)
    grids: Tuple["object", ...]          # QuantGrid per width

    def __post_init__(self):
        assert tuple(sorted(self.widths)) == tuple(self.widths), self.widths
        assert len(self.widths) == len(self.grids)

    @classmethod
    def from_grids(cls, grids_by_bits) -> "PaddedWire":
        items = sorted((int(b), g) for b, g in grids_by_bits.items())
        return cls(tuple(b for b, _ in items), tuple(g for _, g in items))

    @property
    def widest(self) -> int:
        return self.widths[-1]

    def capacity(self, shape) -> int:
        """Physical container bytes for a slab of `shape` (widest codec)."""
        return _body_bytes(self.widest, _n_elements(shape))

    def payload_bytes(self, shape, bits: int) -> int:
        """Logical bytes the ACTIVE codec occupies inside the container."""
        return _body_bytes(int(bits), _n_elements(shape))

    def sel_of_bits(self, bits_seq: Sequence[int]) -> jax.Array:
        """Schedule bits -> traced-able int32 indices into `widths`."""
        return jnp.asarray([self.widths.index(int(b)) for b in bits_seq],
                           jnp.int32)

    def encode(self, x, sel) -> jax.Array:
        cap = self.capacity(x.shape)

        def branch(b, g):
            def f(xx):
                body = ops.pack_codes(g.encode(xx).ravel(), b)
                return jnp.pad(body, (0, cap - body.shape[0]))
            return f

        return jax.lax.switch(
            sel, [branch(b, g) for b, g in zip(self.widths, self.grids)], x)

    def decode(self, container, sel, shape, dtype=jnp.float32) -> jax.Array:
        n = _n_elements(shape)

        def branch(b, g):
            def f(c):
                codes = ops.unpack_codes(c[:_body_bytes(b, n)], b, n)
                return g.decode(codes.reshape(shape), dtype)
            return f

        return jax.lax.switch(
            sel, [branch(b, g) for b, g in zip(self.widths, self.grids)],
            container)


@dataclasses.dataclass(frozen=True)
class ContainerExchange:
    """:class:`NeighborExchange` over a :class:`PaddedWire`: the boundary
    slab ships in the fixed-size container with a traced active width.

    Sender and receiver format independently: ``start_shift_*`` encodes
    with the SENDER's `sel`, ``finish_shift_*`` decodes with `sel_src` —
    the sel the ORIGINATING stage used, which the caller reads from the
    same replicated widths table (index ``(stage ∓ 1) % n``). The split
    halves compose to the fused shifts exactly like `NeighborExchange`.
    """

    axis_name: str
    wire: PaddedWire

    def _perm(self, delta: int):
        n = jax.lax.axis_size(self.axis_name)
        return [(i, (i + delta) % n) for i in range(n)]

    # -- forward shift (out[i] = x[i-1]) ------------------------------------
    def start_shift_from_prev(self, x_loc, sel) -> jax.Array:
        payload = self.wire.encode(x_loc[-1:], sel)
        return jax.lax.ppermute(payload, self.axis_name, self._perm(+1))

    def finish_shift_from_prev(self, payload, x_loc, sel_src):
        boundary = self.wire.decode(payload, sel_src, x_loc[-1:].shape,
                                    x_loc.dtype)
        return jnp.concatenate([boundary, x_loc[:-1]], axis=0)

    def shift_from_prev(self, x_loc, sel_self, sel_src):
        return self.finish_shift_from_prev(
            self.start_shift_from_prev(x_loc, sel_self), x_loc, sel_src)

    # -- backward shift (out[i] = x[i+1]) -----------------------------------
    def start_shift_from_next(self, x_loc, sel) -> jax.Array:
        payload = self.wire.encode(x_loc[:1], sel)
        return jax.lax.ppermute(payload, self.axis_name, self._perm(-1))

    def finish_shift_from_next(self, payload, x_loc, sel_src):
        boundary = self.wire.decode(payload, sel_src, x_loc[:1].shape,
                                    x_loc.dtype)
        return jnp.concatenate([x_loc[1:], boundary], axis=0)

    def shift_from_next(self, x_loc, sel_self, sel_src):
        return self.finish_shift_from_next(
            self.start_shift_from_next(x_loc, sel_self), x_loc, sel_src)

    def wire_bytes(self, boundary_shape) -> int:
        """Physical bytes one shift puts on one link (container capacity)."""
        return self.wire.capacity(boundary_shape)


@dataclasses.dataclass(frozen=True)
class PsumProgramPlan:
    """What a :func:`quantized_psum` trace MUST contain for one
    (codec, world) point — the declarative side of the linter's
    ``schedule.psum_mode`` / ``wire.psum_bytes`` contracts, computed next
    to the mode rule it verifies (:func:`psum_mode`).

      * `collective`      — the physical primitive carrying the payload
        (``all_gather`` on the gather path, ``psum`` otherwise),
      * `operand_dtype`   — that primitive's payload operand dtype (packed
        uint8/uint16 container, int32 code-sum, or raw fp32),
      * `operand_bytes`   — the payload bytes one shard injects, which by
        construction equals ``psum_wire_bytes(...).wire_bytes``,
      * `handshake`       — True iff the affine min/max agreement
        (``pmin``/``pmax``) must appear (static grids need none).
    """
    mode: str
    collective: str
    operand_dtype: str
    operand_bytes: int
    handshake: bool


def psum_program_plan(codec: WireCodec, shape, world_size: int,
                      mode: Optional[str] = None) -> PsumProgramPlan:
    """The traced-program shape :func:`quantized_psum` commits to for this
    (codec, shape, world) point. Byte accounting defers to
    :func:`psum_wire_bytes` so plan and ledger can never disagree."""
    cost = psum_wire_bytes(codec, shape, world_size, mode)
    n = _n_elements(shape)
    if cost.mode == "psum":
        return PsumProgramPlan("psum", "psum", "float32", cost.wire_bytes,
                               False)
    handshake = isinstance(codec, AffineCodec)
    if cost.mode == "gather":
        # the packed container is byte planes whatever the width
        return PsumProgramPlan("gather", "all_gather", "uint8",
                               cost.wire_bytes, handshake)
    assert cost.mode == "code_psum" and cost.wire_bytes == 4 * n
    return PsumProgramPlan("code_psum", "psum", "int32", cost.wire_bytes,
                           handshake)


def record_psum(ledger, iteration: int, edge: str, codec: WireCodec, shape,
                world_size: int, mode: Optional[str] = None) -> PsumWireCost:
    """Put one shard's compressed-psum traffic on the ledger: the payload
    record carries the physical/logical byte split of the SELECTED
    collective, plus the handshake record when the grid needs agreeing."""
    cost = psum_wire_bytes(codec, shape, world_size, mode)
    ledger.record(iteration, edge, "psum", _n_elements(shape), codec.bits,
                  payload_bytes=cost.logical_bytes,
                  wire_bytes=cost.wire_bytes)
    if cost.handshake_bytes:
        ledger.record_handshake(iteration, edge)
    return cost
