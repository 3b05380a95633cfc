"""Deterministic wire fault injection + integrity sentinels for the stage
ring (the fault-tolerance layer of the distributed ADMM runtime).

pdADMM-G provably tolerates inexact updates — a quantized, stale, or even
dropped boundary slab is just one more source of inexactness the ADMM
iteration absorbs (the source paper's pdADMM-G-Q; AdaQP leans on the same
slack). That makes *principled* degraded-mode recovery cheap here: a
detected-corrupt slab is replaced by the last verified one (one extra
iteration of staleness on one boundary), and only an UNDETECTED corruption
that poisons the state (NaN / objective blow-up) needs the heavyweight
response of a checkpoint rollback.

Everything in this module is trace-safe: a :class:`FaultPlan` is a pure
function of ``(seed, tick)`` evaluated on the HOST into a small
:class:`FaultControls` pytree of masks that rides into the compiled step as
a traced argument — the compiled program is identical for every tick and
every plan with the same rate-positivity, so ``n_compiled_steps == 1``
holds under chaos exactly as it does under a schedule change.

Wire integrity header
---------------------
Every sentinel-checked slab flies with a 2-word ``int32`` header next to
the payload (ppermuted through the same ring permutation):

    ``header[0]`` — checksum: wraparound ``int32`` sum of the payload's raw
    container words (uint8/uint16 containers widened to int32, float32
    leaves bit-cast). Headers the codec itself ships (scale/zero) are
    included; only the code body is ever corrupted by the injector.
    ``header[1]`` — seqno: the sender's plan tick. The receiver checks it
    against the tick it EXPECTS (the current tick for fused exchanges, the
    previous tick for a double-buffered carry), which catches delayed /
    stale deliveries that a checksum alone cannot.

The header is 8 physical bytes per slab per link
(:data:`SENTINEL_HEADER_BYTES`), charged to the ledger as ``wire_bytes``
(kind ``"header"``, zero logical payload — integrity overhead is physical,
not part of the compression story).

``metrics["health"]`` schema
----------------------------
Steps built with ``health=True`` (or a fault plan) emit a ``"health"``
block in their metrics, replicated across shards:

    ``wire_bad``        int32 ``[3]`` — failed link verdicts this tick per
                        edge (order :data:`EDGES` = q_fwd, u_fwd, p_bwd),
                        summed over stages AND data-parallel rings.
    ``p_finite`` / ``W_finite`` / ``b_finite`` / ``z_finite``
                        bool — every element of the new iterate is finite.
    ``residual_finite`` bool — residual and objective are finite.
    ``objective_spike`` bool — objective jumped by more than
                        ``SPIKE_TOL * (1 + |prev|)`` over the last accepted
                        objective (``FaultControls.prev_obj``; never fires
                        while ``prev_obj`` is +inf, i.e. at the start).

Failed wire verdicts are RECOVERED in-step (last-good substitution) and do
not make an iteration unhealthy; only non-finite state/metrics or an
objective spike do — those are what undetected (``sneaky``) corruption
causes, and the training loop answers them with checkpoint rollback +
:meth:`BitWidthController.force_widest`.

Fault timing semantics
----------------------
``drop`` and ``flip`` are RECEIVE-time faults (the slab arriving at tick t
is lost / corrupted on the link), so injection tick == detection tick in
both the fused and the double-buffered orderings. ``sneaky`` corrupts the
SENDER's buffer before the checksum is computed — it evades the wire
verdict by construction and lands at tick t fused / t+1 overlapped.
``delay`` (overlap only; ignored by fused steps) makes the receiver's
carry keep the previous in-flight slab, detected one tick later by its
stale seqno. Sneaky/delay events injected on a run's final tick ride a
slab nothing ever consumes and are never observed. Per (edge, src, tick)
the classes are made mutually exclusive at draw time (drop > flip >
sneaky; both shadowed by a previous tick's delay), so every consumed
detectable event produces exactly one failed verdict — that is what makes
``hist["faults"]`` injected-vs-detected accounting exact in tests.

A rollback NEVER rewinds the plan tick: faults are transient events on the
wire, not properties of the iteration number, so a replayed iteration does
not re-suffer them (and a deterministic plan cannot pin a run in an
infinite rollback loop).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm.codecs import WirePayload

# edge order of every per-edge mask / counter in this module
EDGES = ("q_fwd", "u_fwd", "p_bwd")

# physical bytes of the integrity header (2 x int32) per slab per link
SENTINEL_HEADER_BYTES = 8

# objective_spike fires when obj > prev + SPIKE_TOL * (1 + |prev|)
SPIKE_TOL = 10.0


class FaultControls(NamedTuple):
    """The traced per-tick control block a sentinel step consumes (one
    trailing argument, replicated to every shard). Built host-side by
    :meth:`FaultPlan.controls` or :func:`null_controls`."""
    seqno: jax.Array     # int32 [] — the plan tick, stamped into headers
    prev_obj: jax.Array  # f32 []   — last accepted objective (+inf at start)
    flip: jax.Array      # int32 [3, n_stages] — detectable link corruption
    sneaky: jax.Array    # int32 [3, n_stages] — pre-checksum buffer flips
    drop: jax.Array      # bool [3, n_stages]  — lost slabs, by (edge, src)
    delay: jax.Array     # bool [n_stages]     — stale overlap carry, by src
    key: jax.Array       # uint32 [2] — PRNG key for in-trace flip positions


class GoodSlabs(NamedTuple):
    """Last VERIFIED decoded boundary slab per ring edge — the in-carry
    fallback a failed wire verdict substitutes (each ``[1, V_loc, h]``)."""
    q: jax.Array
    u: jax.Array
    p: jax.Array


def null_controls(n_stages: int, seqno: int = 0,
                  prev_obj: float = float("inf")) -> FaultControls:
    """All-clear controls: what a ``health=True, faults=None`` step runs on
    every tick, and the zero-rate template tests compare against."""
    z = jnp.zeros((3, n_stages), jnp.int32)
    return FaultControls(
        seqno=jnp.asarray(seqno, jnp.int32),
        prev_obj=jnp.asarray(prev_obj, jnp.float32),
        flip=z, sneaky=z,
        drop=jnp.zeros((3, n_stages), bool),
        delay=jnp.zeros((n_stages,), bool),
        key=jnp.zeros((2,), jnp.uint32))


@dataclasses.dataclass(frozen=True)
class FaultPlan:
    """Seeded deterministic chaos schedule. Every draw is a pure function of
    ``(seed, tick)`` (``np.random.default_rng((seed, tick))``), so the host
    can re-enumerate the exact injected events (:meth:`events`) for
    accounting, and two runs with the same seed suffer the same faults.

    Rates are per (edge, source stage, tick) Bernoulli probabilities.
    ``blackouts`` silences every outgoing slab of a stage for a tick
    window: ``(stage, start_tick, n_ticks)``."""
    seed: int = 0
    flip_rate: float = 0.0        # detectable: flips AFTER the checksum
    flips_per_event: int = 1      # bit positions XORed per flip event
    sneaky_rate: float = 0.0      # undetectable: flips BEFORE the checksum
    drop_rate: float = 0.0        # slab lost on the link
    delay_rate: float = 0.0       # overlap carry not refreshed (per stage)
    blackouts: Tuple[Tuple[int, int, int], ...] = ()

    def _draw(self, tick: int, n_stages: int):
        """One tick's raw Bernoulli fields + in-trace flip key, with the
        class-exclusion documented in the module docstring applied."""
        rng = np.random.default_rng((int(self.seed), int(tick)))
        drops = rng.random((3, n_stages)) < self.drop_rate
        flips = rng.random((3, n_stages)) < self.flip_rate
        sneaky = rng.random((3, n_stages)) < self.sneaky_rate
        delays = rng.random(n_stages) < self.delay_rate
        key = rng.integers(0, 2 ** 32, size=2, dtype=np.uint32)
        for (stage, start, n) in self.blackouts:
            if start <= tick < start + n:
                drops[:, stage] = True
        # exclusivity: drop > flip > sneaky per (edge, src); a delayed
        # carry shadows next tick's q/u faults from the same source (the
        # stale slab already fails its seqno check — one verdict per slab).
        # The delay exclusion reads the PRISTINE drops so that
        # `_draw_delays` is an exact one-tick recursion (no k-2 coupling).
        delays &= ~drops[0] & ~drops[1]   # a dropped slab can't also be late
        flips &= ~drops
        sneaky &= ~drops & ~flips
        if tick > 0:
            prev = self._draw_delays(tick - 1, n_stages)
            for fld in (drops, flips, sneaky):
                fld[:2, prev] = False
        return drops, flips, sneaky, delays, key

    def _draw_delays(self, tick: int, n_stages: int) -> np.ndarray:
        rng = np.random.default_rng((int(self.seed), int(tick)))
        rng.random((3, n_stages))          # drops
        rng.random((3, n_stages))          # flips
        rng.random((3, n_stages))          # sneaky
        raw = rng.random(n_stages) < self.delay_rate
        drops = self._draw_drops_only(tick, n_stages)
        return raw & ~drops[0] & ~drops[1]

    def _draw_drops_only(self, tick: int, n_stages: int) -> np.ndarray:
        rng = np.random.default_rng((int(self.seed), int(tick)))
        drops = rng.random((3, n_stages)) < self.drop_rate
        for (stage, start, n) in self.blackouts:
            if start <= tick < start + n:
                drops[:, stage] = True
        return drops

    @property
    def active(self) -> bool:
        """Whether this plan can ever inject anything (a fully zero-rate
        plan still traces the injection machinery — the compiled program is
        a property of the plan OBJECT, not its rates — but behaves as the
        all-clear controls bit-for-bit)."""
        return (self.flip_rate > 0 or self.sneaky_rate > 0
                or self.drop_rate > 0 or self.delay_rate > 0
                or bool(self.blackouts))

    def controls(self, tick: int, n_stages: int, *,
                 prev_obj: float = float("inf")) -> FaultControls:
        """The traced control block for one tick."""
        drops, flips, sneaky, delays, key = self._draw(tick, n_stages)
        return FaultControls(
            seqno=jnp.asarray(tick, jnp.int32),
            prev_obj=jnp.asarray(prev_obj, jnp.float32),
            flip=jnp.asarray(flips, jnp.int32),
            sneaky=jnp.asarray(sneaky, jnp.int32),
            drop=jnp.asarray(drops),
            delay=jnp.asarray(delays),
            key=jnp.asarray(key))

    def events(self, tick: int, n_stages: int):
        """Host-side trace of the events injected at `tick`: a list of
        ``(edge_name, src_stage, kind)`` with kind in ``{"drop", "flip",
        "sneaky", "delay"}`` (blackout ticks surface as drops on every
        edge). Pure function of (seed, tick) — re-enumerable at any time,
        which is how ``hist["faults"]`` accounts every injection."""
        drops, flips, sneaky, delays, _ = self._draw(tick, n_stages)
        ev = []
        for kind, fld in (("drop", drops), ("flip", flips),
                          ("sneaky", sneaky)):
            for e in range(3):
                for s in range(n_stages):
                    if fld[e, s]:
                        ev.append((EDGES[e], s, kind))
        for s in range(n_stages):
            if delays[s]:
                # a stale carry fails BOTH forward slabs' seqno checks
                ev.append((EDGES[0], s, "delay"))
                ev.append((EDGES[1], s, "delay"))
        return ev

    def trace(self, n_ticks: int, n_stages: int):
        """events() over ticks [0, n_ticks) as ``(tick, edge, src, kind)``."""
        return [(t, e, s, k) for t in range(int(n_ticks))
                for (e, s, k) in self.events(t, n_stages)]


@dataclasses.dataclass(frozen=True)
class RecoveryConfig:
    """Rollback policy knobs for the fault-tolerant training loops."""
    cooldown: int = 4        # control steps forced to the widest width
    max_rollbacks: int = 8   # raise after this many (divergence, not chaos)


# ---------------------------------------------------------------------------
# In-trace primitives: checksum + bit flips
# ---------------------------------------------------------------------------

_UINT_OF_WIDTH = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}


def _as_int32_words(x: jax.Array) -> jax.Array:
    """Same-bits int32 word view of a payload leaf (checksum domain)."""
    if x.dtype in (jnp.uint8, jnp.uint16):
        return x.astype(jnp.int32)
    if x.dtype in (jnp.int32,):
        return x
    if x.dtype.itemsize == 4:                      # float32 / uint32
        return jax.lax.bitcast_convert_type(x, jnp.int32)
    raise TypeError(f"no checksum word view for dtype {x.dtype}")


def payload_checksum(payload) -> jax.Array:
    """Wraparound int32 sum over every word of every payload leaf — the
    header's integrity word. An XOR of any single bit always changes it
    (each word contributes its exact value), so every non-sneaky flip is
    detected; it is NOT cryptographic and colliding multi-word corruptions
    exist — those land in the same bucket as sneaky flips and fall through
    to the finite/spike sentinels."""
    total = jnp.zeros((), jnp.int32)
    for leaf in jax.tree.leaves(payload):
        total = total + jnp.sum(_as_int32_words(leaf), dtype=jnp.int32)
    return total


def checksum_header(payload, seqno) -> jax.Array:
    """``[checksum, seqno]`` int32[2] — the wire integrity header."""
    return jnp.stack([payload_checksum(payload),
                      jnp.asarray(seqno, jnp.int32)])


def verify_header(payload, header, expected_seqno) -> jax.Array:
    """Link verdict: checksum matches AND the slab is the expected tick's."""
    return ((payload_checksum(payload) == header[0])
            & (header[1] == jnp.asarray(expected_seqno, jnp.int32)))


def flip_bits(x: jax.Array, key: jax.Array, n_flips: int,
              active) -> jax.Array:
    """XOR `n_flips` uniformly-drawn bit positions of `x`'s raw container
    when ``active`` is nonzero; bit-exact identity otherwise. The machinery
    always traces (static shapes) — ``active`` only zeroes the XOR mask, so
    one compiled program serves faulty and clean ticks alike."""
    dt = x.dtype
    u = _UINT_OF_WIDTH[dt.itemsize]
    width = dt.itemsize * 8
    raw = x if dt == u else jax.lax.bitcast_convert_type(x, u)
    flat = raw.ravel()
    nbits = flat.shape[0] * width
    if nbits == 0:
        return x
    act = jnp.asarray(active, jnp.int32) > 0
    for i in range(int(n_flips)):
        pos = jax.random.randint(jax.random.fold_in(key, i), (), 0, nbits)
        idx = pos // width
        mask = (jnp.uint32(1) << jnp.uint32(pos % width)).astype(u)
        mask = jnp.where(act, mask, jnp.zeros((), u))
        flat = flat.at[idx].set(flat[idx] ^ mask)
    out = flat.reshape(raw.shape)
    return out if dt == u else jax.lax.bitcast_convert_type(out, dt)


def flip_payload(payload, key: jax.Array, n_flips: int, active):
    """Corrupt the CODE BODY of a wire payload (the codes leaf of a
    :class:`WirePayload`, or a flat container array); codec headers
    (scale/zero) fly untouched."""
    if isinstance(payload, WirePayload):
        return payload._replace(
            codes=flip_bits(payload.codes, key, n_flips, active))
    return flip_bits(payload, key, n_flips, active)


# ---------------------------------------------------------------------------
# Sentinel-wrapped boundary exchange
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SentinelExchange:
    """A ring boundary exchange with the integrity header and the fault
    injector wired around it. Wraps either a codec wire (``codec=``, the
    :class:`~repro.comm.transport.NeighborExchange` format) or a padded
    container (``wire=``, the :class:`~repro.comm.transport.PaddedWire`
    format); `edge` indexes :data:`EDGES` and selects this exchange's row
    of every per-edge control mask.

    ``start`` returns the in-flight ``(payload, header)`` pair (both
    already ppermuted — carryable through a scan like the plain split
    halves); ``finish`` verifies, decodes, and substitutes `good` on a
    failed verdict, returning ``(boundary, ok)``. With ``plan=None`` the
    header machinery still runs (health sentinels without chaos) but no
    injection traces."""

    axis_name: str
    edge: int
    codec: Optional[object] = None         # WireCodec
    wire: Optional[object] = None          # PaddedWire
    plan: Optional[FaultPlan] = None

    def _perm(self, delta: int):
        n = jax.lax.axis_size(self.axis_name)
        return [(i, (i + delta) % n) for i in range(n)]

    def _encode(self, slab, sel):
        if self.wire is not None:
            return self.wire.encode(slab, sel)
        return self.codec.encode(slab)

    def _decode(self, payload, shape, dtype, sel_src):
        if self.wire is not None:
            return self.wire.decode(payload, sel_src, shape, dtype)
        return self.codec.decode(payload, shape=shape, dtype=dtype)

    def start(self, slab, ctl: FaultControls, delta: int, sel=None):
        """Encode the boundary slab, stamp the header, apply SEND-time
        faults (sneaky pre-checksum corruption), and issue the ppermute
        pair. `delta` is the ring direction (+1 from-prev, -1 from-next)."""
        payload = self._encode(slab, sel)
        if self.plan is not None:
            sidx = jax.lax.axis_index(self.axis_name)
            k = jax.random.fold_in(jax.random.fold_in(ctl.key, self.edge),
                                   sidx)
            payload = flip_payload(payload, jax.random.fold_in(k, 0),
                                   self.plan.flips_per_event,
                                   ctl.sneaky[self.edge, sidx])
        header = checksum_header(payload, ctl.seqno)
        perm = self._perm(delta)
        fly = jax.tree.map(
            lambda t: jax.lax.ppermute(t, self.axis_name, perm), payload)
        hdr = jax.lax.ppermute(header, self.axis_name, perm)
        return fly, hdr

    def finish(self, fly, ctl: FaultControls, expected_seqno, shape, dtype,
               good, delta: int, sel_src=None):
        """Apply RECEIVE-time faults (link flip/drop, keyed by the SOURCE
        stage), verify the header, decode, and substitute `good` when the
        verdict fails. Returns ``(boundary [1,V_loc,h], ok scalar bool)``."""
        payload, header = fly
        sidx = jax.lax.axis_index(self.axis_name)
        n = jax.lax.axis_size(self.axis_name)
        src = jnp.mod(sidx - delta, n)
        if self.plan is not None:
            k = jax.random.fold_in(jax.random.fold_in(ctl.key, self.edge),
                                   src)
            payload = flip_payload(payload, jax.random.fold_in(k, 1),
                                   self.plan.flips_per_event,
                                   ctl.flip[self.edge, src])
        ok = verify_header(payload, header, expected_seqno)
        if self.plan is not None:
            ok = ok & ~ctl.drop[self.edge, src]
        boundary = self._decode(payload, shape, dtype, sel_src)
        return jnp.where(ok, boundary, good), ok
