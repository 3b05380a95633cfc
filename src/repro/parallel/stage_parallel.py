"""Stage-parallel pdADMM-G on a (data, model) mesh — the paper's model
parallelism made TPU-native.

Mapping (DESIGN.md §2):
  * layer-clients  -> mesh stages: homogeneous h→h layers stacked [L, ...],
    sharded over the `model` axis; all six updates are batched over the local
    layer block with `vmap` (they only read previous-iteration neighbors, so
    there is NO intra-iteration dependency between layers — Algorithm 1).
  * node dimension |V| -> sharded over `data` (+`pod`): W replicated, p/q/z/u
    row-sharded; the inner-loop matmuls need no collectives.
  * NCCL send/recv of p/q/u -> one forward and one backward `ppermute`
    neighbor shift per iteration, int8/int16-encoded on the wire when
    quantization is on (pdADMM-G-Q) — this is the paper's 45% comm saving as
    ICI payload reduction, visible in the lowered HLO.

Homogenization (documented DESIGN.md §7): the distributed model applies a
fixed random projection X @ P0 (n0 -> h) as preprocessing (alongside Ψ), and
the risk reads the first C columns of the last layer's z. First/last layer
special cases are handled with per-layer masks, keeping every stage's compute
identical (no load imbalance — the paper's equal-width large-scale setup).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.comm import faults as FT
from repro.comm.codecs import (FP32, Fp32Codec, GridCodec, WireCodec,
                               WirePayload, codec_for_grid)
from repro.comm.transport import (ContainerExchange, NeighborExchange,
                                  PaddedWire)
from repro.core import subproblems as sp
from repro.core.pdadmm import ADMMConfig, relu, run_chunked
from repro.core.quantize import QuantGrid


class StackState(NamedTuple):
    """All leaves stacked over layers: W [L,h,h], b [L,h], others [L,V,h]."""
    p: jax.Array
    W: jax.Array
    b: jax.Array
    z: jax.Array
    q: jax.Array
    u: jax.Array


def init_stack(key, Xp, L: int, config: ADMMConfig) -> StackState:
    """Xp: [V, h] (already projected). Forward-consistent init."""
    V, h = Xp.shape
    keys = jax.random.split(key, L)
    Ws, zs, ps, qs = [], [], [], []
    cur = Xp
    for l in range(L):
        Wl = jax.random.normal(keys[l], (h, h), jnp.float32) * jnp.sqrt(2.0 / h)
        zl = cur @ Wl
        ql = relu(zl)
        if config.quantize_p and config.grid is not None:
            ql = config.grid.project(ql)
        Ws.append(Wl)
        ps.append(cur)
        zs.append(zl)
        qs.append(ql)
        cur = ql
    return StackState(
        p=jnp.stack(ps), W=jnp.stack(Ws), b=jnp.zeros((L, h), jnp.float32),
        z=jnp.stack(zs), q=jnp.stack(qs), u=jnp.zeros((L, V, h), jnp.float32))


# ---------------------------------------------------------------------------
# Neighbor exchange: local roll + boundary ppermute. ALL wire formatting goes
# through repro.comm (codec-formatted NeighborExchange); these wrappers only
# keep the historical grid-based signature alive for external callers.
# ---------------------------------------------------------------------------

def shift_from_prev(x_loc, axis_name: str, grid: Optional[QuantGrid] = None):
    """Per local stack [M,V,h]: return previous layer's value per layer:
    out[i] = x[i-1], with x[-1] fetched from the previous stage (garbage into
    global layer 0, which is masked by the caller)."""
    return NeighborExchange(axis_name, codec_for_grid(grid)) \
        .shift_from_prev(x_loc)


def shift_from_next(x_loc, axis_name: str, grid: Optional[QuantGrid] = None):
    """out[i] = x[i+1]; x[M] fetched from the next stage (garbage into global
    layer L-1, masked by the caller)."""
    return NeighborExchange(axis_name, codec_for_grid(grid)) \
        .shift_from_next(x_loc)


def _dp_axes(mesh: Mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def stack_partition_specs(mesh: Mesh) -> StackState:
    """PartitionSpecs of a :class:`StackState` on `mesh`: layers over the
    `model` axis, nodes over the data axes, W/b replicated over data."""
    dp = _dp_axes(mesh)
    return StackState(
        p=P("model", dp), W=P("model"), b=P("model"),
        z=P("model", dp), q=P("model", dp), u=P("model", dp))


def _payload_spec(codec: WireCodec, dp) -> WirePayload:
    """PartitionSpec tree of one in-flight boundary payload as a GLOBAL
    array (the `overlap=True` scan carry): header-free codecs only — the
    stage ring's grid/fp32 wire keeps the slab shape [1, V_loc, h] per
    shard (nibble-packed int4 flattens, so every axis rides dim 0)."""
    if isinstance(codec, PaddedWire):
        # flat uint8 container per shard: every axis rides dim 0
        return P(("model",) + dp)
    if not isinstance(codec, (Fp32Codec, GridCodec)):
        raise ValueError(
            "overlap carries in-flight encoded slabs across iterations, "
            "which needs a header-free wire format (grid or fp32 codec); "
            f"got {codec.name}")
    codes = P(("model",) + dp) if codec.bits <= 4 else P("model", dp)
    return WirePayload(codes, None, None)


# ---------------------------------------------------------------------------
# One distributed iteration (runs inside shard_map, per (data, model) shard)
# ---------------------------------------------------------------------------

def _masked_ce_val(z, labels, label_mask, n_classes: int):
    """Risk value on z[:, :C] (head folded into last layer). The matching
    gradient lives in `subproblems.ce_grad_cols` and reaches the z-solve
    only through the `ops.fista_zlast` dispatch."""
    zc = z[:, :n_classes]
    logp = jax.nn.log_softmax(zc, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(nll * label_mask)


def _fista_last(a, z_old, labels, label_mask, nu, n_classes, n_iters,
                use_kernels: bool = True):
    """Head-folded z_L solve of a stage's [M, V, h] layer stack, on the one
    layer whose result can be kept: global layer L-1 is always a stage's
    LAST local layer, so only ``a[-1]`` is solved and the caller selects it
    on the stage that holds L-1 (every other layer, and that layer on every
    other stage, takes the z-hidden update). Inside the layer
    `subproblems.update_z_last` iterates on the C logit columns alone and
    writes the rest in closed form. Returns the [V, h] solution."""
    return sp.update_z_last(a[-1], z_old[-1], labels, label_mask, nu,
                            n_iters, n_classes=n_classes,
                            use_kernels=use_kernels)


def make_distributed_step(mesh: Mesh, L: int, n_classes: int,
                          config: ADMMConfig, *, overlap: bool = False,
                          donate: bool = False,
                          p_codec: Optional[WireCodec] = None,
                          q_codec: Optional[WireCodec] = None,
                          wire: Optional[PaddedWire] = None,
                          health: bool = False,
                          faults: Optional[FT.FaultPlan] = None):
    """Build the jit-able distributed ADMM iteration; returns (step, specs).

    overlap=False (the paper-faithful ordering): ``step(state, Xp, labels,
    label_mask) -> (state, metrics)``, with every boundary exchange fused —
    encode, ppermute and decode issued exactly where the value is consumed.

    overlap=True (double-buffered boundary slabs): ``step((state, inflight),
    Xp, labels, label_mask) -> ((state, inflight), metrics)``. The q/u
    forward exchange that iteration k+1 consumes *at entry* is STARTED at
    the end of iteration k (right after the q/dual updates produce those
    exact values) and only FINISHED — decoded and spliced — at the entry of
    k+1, so the in-flight encoded slabs cross the iteration boundary in the
    carry and the ring messages hide behind the tail metrics psums and the
    entry residual computation. The within-iteration backward p exchange is
    likewise started right after the p-solve and finished right before the
    q-update that consumes it, putting the whole W/b/z solve family between
    issue and use. Because every shift exchanges exactly the values the
    non-overlap ordering exchanges (the split halves compose to the fused
    shift), overlap=True is bitwise-identical in state and metrics — it
    changes WHEN bytes move, never what or how many. Prime the first
    iteration's carry with :func:`make_overlap_primer` (or use
    ``distributed_train(..., overlap=True)``, which does both).

    `p_codec`/`q_codec` override the wire format derived from `config` (the
    adaptive controller path swaps codecs between cached compilations; the
    wire format is static per compiled step, so SPMD stages stay uniform).
    overlap requires header-free codecs (grid/fp32 — the stage-ring formats)
    because the in-flight payload is carried as a plain sharded array.

    `wire` (a :class:`PaddedWire`) switches the p/q boundary exchange to
    padded fixed-size uint8 containers: the step then takes a trailing
    ``widths`` argument — an int32 ``[2, n_stages]`` table (row 0 = q sel,
    row 1 = p sel; indices into ``wire.widths``) — and each stage's
    exchanges run at ITS OWN traced bit-width while the compiled program
    (and the physical ppermute payload, sized for the widest codec) stays
    schedule-independent: per-boundary, per-iteration mixed widths with
    exactly one compilation. Mutually exclusive with `p_codec`/`q_codec`;
    u still flies fp32.

    `health=True` (or any `faults=` plan) builds the SENTINEL step: every
    boundary slab flies with the int32[2] checksum/seqno integrity header
    (:mod:`repro.comm.faults` documents the format), the carry grows a
    :class:`~repro.comm.faults.GoodSlabs` of last-verified boundaries
    (``state`` becomes ``(StackState, GoodSlabs)``; under overlap each
    in-flight slab becomes a ``(payload, header)`` pair), the step takes a
    trailing :class:`~repro.comm.faults.FaultControls` argument, and
    ``metrics["health"]`` reports wire verdicts / finite checks / the
    objective-spike flag. A failed wire verdict substitutes the last-good
    slab in-step (inexact-ADMM-legal). `faults=` additionally traces the
    deterministic injector around each exchange; with the default
    ``health=False, faults=None`` the compiled program, carry layout and
    metrics are exactly the pre-sentinel ones. Prime the GoodSlabs carry
    with :func:`make_sentinel_primer` (and the overlap carry with
    ``make_overlap_primer(..., sentinel=True)``).
    """
    nu, rho = config.nu, config.rho
    p_grid = config.grid if config.quantize_p else None
    q_grid = config.grid if config.quantize_q else None
    assert wire is None or (p_codec is None and q_codec is None), \
        "wire= (padded containers) replaces the static p/q codecs"
    if p_codec is None:
        p_codec = codec_for_grid(p_grid)
    if q_codec is None:
        q_codec = codec_for_grid(q_grid)
    ex_p = NeighborExchange("model", p_codec)
    ex_q = NeighborExchange("model", q_codec)
    ex_u = NeighborExchange("model", FP32)
    cex = None if wire is None else ContainerExchange("model", wire)
    sentinel = bool(health) or faults is not None
    if sentinel:
        sx_q = FT.SentinelExchange(
            "model", 0, codec=None if wire is not None else q_codec,
            wire=wire, plan=faults)
        sx_u = FT.SentinelExchange("model", 1, codec=FP32, plan=faults)
        sx_p = FT.SentinelExchange(
            "model", 2, codec=None if wire is not None else p_codec,
            wire=wire, plan=faults)
    dp = _dp_axes(mesh)
    n_stages = mesh.shape["model"]
    assert L % n_stages == 0, (L, n_stages)
    m_loc = L // n_stages

    stack_specs = stack_partition_specs(mesh)

    uk = config.use_kernels

    def stage_body(carry, Xp, labels, label_mask, widths=None, ctl=None):
        # Each phase runs under a `jax.named_scope` ("admm.<solve>"), which
        # names it in the op_name metadata of every HLO instruction it
        # lowers to, so a device trace can be summed per solve. The scopes
        # are metadata only: the compiled program is the same without them.
        if overlap:
            st_c, (q_fly, u_fly) = carry
        else:
            st_c = carry
        if sentinel:
            st, good = st_c
        else:
            st = st_c
        sidx = jax.lax.axis_index("model")
        gidx = sidx * m_loc + jnp.arange(m_loc)          # global layer ids
        is_first = (gidx == 0)[:, None, None]
        is_last = (gidx == L - 1)[:, None, None]

        # ---- neighbor exchange (prev iteration values) -------------------
        # overlap: the ppermutes were issued at the END of the previous
        # iteration (same values — st.q/st.u ARE that iteration's outputs);
        # only decode+splice happens here.
        with jax.named_scope("admm.exchange"):
            if cex is not None:
                # active widths: mine for encodes, the ORIGINATING stage's
                # for decodes (everyone reads the same replicated table)
                sel_q, sel_p = widths[0, sidx], widths[1, sidx]
                sel_q_prev = widths[0, jnp.mod(sidx - 1, n_stages)]
                sel_p_next = widths[1, jnp.mod(sidx + 1, n_stages)]
            if sentinel:
                # a carried slab was stamped by last tick's controls
                exp_qu = ctl.seqno - 1 if overlap else ctl.seqno
                slab_shape = st.q[-1:].shape
                if not overlap:
                    q_fly = sx_q.start(st.q[-1:], ctl, +1,
                                       sel=sel_q if cex is not None else None)
                    u_fly = sx_u.start(st.u[-1:], ctl, +1)
                qb, ok_q = sx_q.finish(
                    q_fly, ctl, exp_qu, slab_shape, st.q.dtype, good.q, +1,
                    sel_src=sel_q_prev if cex is not None else None)
                ub, ok_u = sx_u.finish(u_fly, ctl, exp_qu, slab_shape,
                                       st.u.dtype, good.u, +1)
                q_prev = jnp.concatenate([qb, st.q[:-1]], axis=0)
                u_prev = jnp.concatenate([ub, st.u[:-1]], axis=0)
                good_q, good_u = qb, ub
            elif overlap:
                q_prev = (cex.finish_shift_from_prev(q_fly, st.q, sel_q_prev)
                          if cex is not None
                          else ex_q.finish_shift_from_prev(q_fly, st.q))
                u_prev = ex_u.finish_shift_from_prev(u_fly, st.u)
            elif cex is not None:
                q_prev = cex.shift_from_prev(st.q, sel_q, sel_q_prev)
                u_prev = ex_u.shift_from_prev(st.u)
            else:
                q_prev = ex_q.shift_from_prev(st.q)
                u_prev = ex_u.shift_from_prev(st.u)
            q_prev = jnp.where(is_first, 0.0, q_prev)    # layer 0 has no prev
            u_prev = jnp.where(is_first, 0.0, u_prev)

        # ---- entry residuals r = z - pW - b (ONE fused op per layer);
        # chained through the whole update family below, so no solver ever
        # recomputes the linear map and backtracking trials are matmul-free.
        with jax.named_scope("admm.residual"):
            r = jax.vmap(
                lambda p_, W_, b_, z_: sp._residual(p_, W_, b_, z_, uk))(
                    st.p, st.W, st.b, st.z)

        # ---- p-update (masked for layer 0: p0 = Xp fixed) -----------------
        def p_upd(p_, W_, b_, z_, qp, up, r_):
            pn, _, rn = sp.update_p(p_, W_, b_, z_, qp, up, nu, rho,
                                    config.tau0, grid=p_grid, r0=r_,
                                    use_kernels=uk)
            return pn, rn
        with jax.named_scope("admm.p"):
            p_new, r_new = jax.vmap(p_upd)(st.p, st.W, st.b, st.z, q_prev,
                                           u_prev, r)
            p = jnp.where(is_first, Xp[None], p_new)
            r = jnp.where(is_first, r, r_new)  # layer 0 keeps the Xp residual

        # overlap: issue the backward p exchange as soon as the p-solve is
        # done — the W/b/z solves below never read p_next, so the message
        # rides under them and is finished right before the q-update.
        if overlap:
            with jax.named_scope("admm.exchange"):
                if sentinel:
                    p_fly = sx_p.start(p[:1], ctl, -1,
                                       sel=sel_p if cex is not None else None)
                else:
                    p_fly = (cex.start_shift_from_next(p, sel_p)
                             if cex is not None
                             else ex_p.start_shift_from_next(p))

        # ---- W-update ------------------------------------------------------
        def W_upd(p_, W_, b_, z_, qp, up, r_):
            # For layer 0 the dual/penalty terms are constants wrt W, so the
            # same formula with zeroed (qp, up) is EXACT for the W gradient.
            Wn, _, rn = sp.update_W(p_, W_, b_, z_, qp, up, nu, rho,
                                    config.tau0, first=False, r0=r_,
                                    use_kernels=uk)
            return Wn, rn
        with jax.named_scope("admm.W"):
            W, r = jax.vmap(W_upd)(p, st.W, st.b, st.z, q_prev, u_prev, r)

        # ---- b-update (exact: b += mean r; matmul-free) -------------------
        with jax.named_scope("admm.b"):
            db = jnp.mean(r, axis=1)
            b = st.b + db
            r = r - db[:, None, :]

        # ---- z-update (a = pW + b = z - r; matmul-free) --------------------
        with jax.named_scope("admm.z"):
            a = st.z - r
            z_hidden = sp._zupdate(a, st.q, st.z, nu, uk)
        with jax.named_scope("admm.z_last"):
            z_last = _fista_last(a, st.z, labels, label_mask, nu, n_classes,
                                 config.fista_iters, use_kernels=uk)
        with jax.named_scope("admm.z"):
            # a select over the stack, not an update of its last slab: XLA
            # fuses it into the metrics' passes over z and writes z straight
            # into the loop carry, where an in-place slab update costs a
            # full copy of the stack into the carry
            z = jnp.where(is_last, z_last[None], z_hidden)

        # ---- q-update (needs p_{l+1} = next layer's NEW p) -------------------
        with jax.named_scope("admm.exchange"):
            if sentinel:
                # the backward p slab always flies within its own tick
                if not overlap:
                    p_fly = sx_p.start(p[:1], ctl, -1,
                                       sel=sel_p if cex is not None else None)
                pb, ok_p = sx_p.finish(
                    p_fly, ctl, ctl.seqno, p[:1].shape, p.dtype, good.p, -1,
                    sel_src=sel_p_next if cex is not None else None)
                p_next = jnp.concatenate([p[1:], pb], axis=0)
                good_p = pb
            elif cex is not None:
                p_next = (cex.finish_shift_from_next(p_fly, p, sel_p_next)
                          if overlap else
                          cex.shift_from_next(p, sel_p, sel_p_next))
            else:
                p_next = (ex_p.finish_shift_from_next(p_fly, p) if overlap
                          else ex_p.shift_from_next(p))
        with jax.named_scope("admm.q"):
            fz = relu(z)
            q = jax.vmap(sp.update_q, in_axes=(0, 0, 0, None, None, None))(
                p_next, st.u, fz, nu, rho, q_grid)
            q = jnp.where(is_last, st.q, q)              # no q for layer L-1

        # ---- dual update ------------------------------------------------------
        with jax.named_scope("admm.u"):
            r = jnp.where(is_last, 0.0, p_next - q)
            u = st.u + rho * r

        # overlap: q and u now hold exactly the values the NEXT iteration's
        # entry exchange would send — start the forward shifts here so the
        # ring messages fly under the metrics psums below and next entry's
        # residual computation, and carry the encoded slabs across.
        if overlap:
            with jax.named_scope("admm.exchange"):
                if sentinel:
                    new_q_fly = sx_q.start(
                        q[-1:], ctl, +1,
                        sel=sel_q if cex is not None else None)
                    new_u_fly = sx_u.start(u[-1:], ctl, +1)
                    if faults is not None:
                        # delayed delivery: MY carry keeps the stale pair
                        # when my upstream source's send is late (detected
                        # next tick by the stale seqno in the carried header)
                        late = ctl.delay[jnp.mod(sidx - 1, n_stages)]
                        hold = lambda old, fresh: jax.tree.map(
                            lambda o, f: jnp.where(late, o, f), old, fresh)
                        new_q_fly = hold(q_fly, new_q_fly)
                        new_u_fly = hold(u_fly, new_u_fly)
                    out_fly = (new_q_fly, new_u_fly)
                else:
                    out_fly = ((cex.start_shift_from_prev(q, sel_q)
                                if cex is not None
                                else ex_q.start_shift_from_prev(q)),
                               ex_u.start_shift_from_prev(u))

        # ---- metrics ------------------------------------------------------------
        with jax.named_scope("admm.metrics"):
            res_sq = jax.lax.psum(jnp.sum(r * r), ("model",) + dp)
            # per-stage primal residual (the controller's per-boundary
            # signal): each stage drops its local ||p_next - q||^2 into its
            # slot, psum assembles the replicated [n_stages] vector
            seg = jnp.zeros((n_stages,), jnp.float32).at[sidx].set(
                jnp.sum(r * r))
            seg = jax.lax.psum(seg, ("model",) + dp)
            risk_val = _masked_ce_val(z[-1], labels, label_mask, n_classes)
            risk_val = jnp.where(sidx == n_stages - 1, risk_val, 0.0)
            risk_val = jax.lax.psum(risk_val, "model")
            risk_val = jax.lax.psum(risk_val, dp) if dp else risk_val
            lag = _local_lagrangian(StackState(p, W, b, z, q, u),
                                    r + (z - st.z), q_prev, u_prev,
                                    is_first, is_last, nu, rho)
            lag = jax.lax.psum(lag, ("model",) + dp) + risk_val
            new = StackState(p, W, b, z, q, u)
            metrics = {"residual": jnp.sqrt(res_sq), "objective": lag,
                       "stage_residuals": jnp.sqrt(seg)}
            if sentinel:
                axes = ("model",) + dp
                i32 = jnp.int32

                def all_finite(t):
                    return jax.lax.psum(
                        jnp.sum(~jnp.isfinite(t), dtype=i32), axes) == 0

                metrics["health"] = {
                    "wire_bad": jnp.stack(
                        [jax.lax.psum((~o).astype(i32), axes)
                         for o in (ok_q, ok_u, ok_p)]),
                    "p_finite": all_finite(p), "W_finite": all_finite(W),
                    "b_finite": all_finite(b), "z_finite": all_finite(z),
                    "residual_finite": (jnp.isfinite(res_sq)
                                        & jnp.isfinite(lag)),
                    "objective_spike": (
                        jnp.isfinite(ctl.prev_obj)
                        & (lag > ctl.prev_obj
                           + FT.SPIKE_TOL * (1.0 + jnp.abs(ctl.prev_obj)))),
                }
                out_state = (new, FT.GoodSlabs(q=good_q, u=good_u, p=good_p))
            else:
                out_state = new
        return ((out_state, out_fly) if overlap else out_state), metrics

    def _local_lagrangian(st, rr, q_prev, u_prev, is_first, is_last, nu, rho):
        # rr = z - pW - b at the NEW iterate, chained from the update family
        # (zero extra matmuls vs re-deriving each layer's linear map).
        val = 0.5 * nu * jnp.sum(rr * rr)
        g = jnp.where(is_last, 0.0, st.q - relu(st.z))
        val += 0.5 * nu * jnp.sum(g * g)
        d = jnp.where(is_first, 0.0, st.p - q_prev)
        val += jnp.sum(u_prev * d) + 0.5 * rho * jnp.sum(d * d)
        return val

    slab_spec = P("model", dp)
    state_specs = ((stack_specs,
                    FT.GoodSlabs(slab_spec, slab_spec, slab_spec))
                   if sentinel else stack_specs)
    if overlap:
        hdr_spec = P(("model",) + dp)

        def fly_spec(c):
            ps = _payload_spec(c, dp)
            return (ps, hdr_spec) if sentinel else ps

        carry_specs = (state_specs,
                       (fly_spec(wire if wire is not None else q_codec),
                        fly_spec(FP32)))
    else:
        carry_specs = state_specs
    if wire is not None or sentinel:
        # trailing replicated extras: the widths table (wire path) and the
        # FaultControls block (sentinel path), in that order
        extra_specs = (P(),) * ((wire is not None) + sentinel)

        def wrapped(c, Xp, lab, msk, *extra):
            return stage_body(
                c, Xp, lab, msk,
                widths=extra[0] if wire is not None else None,
                ctl=extra[-1] if sentinel else None)

        smapped = shard_map(
            wrapped, mesh=mesh,
            in_specs=(carry_specs, P(dp), P(dp), P(dp)) + extra_specs,
            out_specs=(carry_specs, P()),
            check_vma=False)
    else:
        smapped = shard_map(
            lambda c, Xp, lab, msk: stage_body(c, Xp, lab, msk), mesh=mesh,
            in_specs=(carry_specs, P(dp), P(dp), P(dp)),
            out_specs=(carry_specs, P()),
            check_vma=False)

    return jax.jit(smapped, donate_argnums=(0,) if donate else ()), stack_specs


def make_overlap_primer(mesh: Mesh, q_codec: WireCodec = FP32, *,
                        wire: Optional[PaddedWire] = None,
                        sentinel: bool = False):
    """Start the FIRST iteration's forward q/u boundary exchange for an
    ``overlap=True`` step: ``prime(q, u) -> (q_payload, u_payload)`` — the
    in-flight carry half. `q_codec` must match the step's q wire (u always
    flies fp32, as in `make_distributed_step`). With `wire` (the padded-
    container step) the primer is ``prime(q, u, widths)`` — the q slab is
    encoded into the container at the widths table's traced q sels, so one
    compiled primer serves every schedule.

    `sentinel=True` primes the carry of a ``health=/faults=`` step: the
    primer takes a trailing traced ``seqno`` (stamp it with ``tick - 1`` —
    the tick whose tail WOULD have issued this exchange) and each fly half
    becomes the sentinel ``(payload, header)`` pair. Priming is always
    clean: no injection, a fresh checksum."""
    dp = _dp_axes(mesh)
    ex_q = NeighborExchange("model", q_codec)
    ex_u = NeighborExchange("model", FP32)
    cex = None if wire is None else ContainerExchange("model", wire)
    n_stages = mesh.shape["model"]
    if sentinel:
        sx_q = FT.SentinelExchange(
            "model", 0, codec=None if wire is not None else q_codec,
            wire=wire, plan=None)
        sx_u = FT.SentinelExchange("model", 1, codec=FP32, plan=None)
        hdr_spec = P(("model",) + dp)

        def prime_s(q, u, seqno):
            ctl = FT.null_controls(n_stages, seqno=seqno)
            return (sx_q.start(q[-1:], ctl, +1), sx_u.start(u[-1:], ctl, +1))

        def prime_container_s(q, u, widths, seqno):
            ctl = FT.null_controls(n_stages, seqno=seqno)
            sel_q = widths[0, jax.lax.axis_index("model")]
            return (sx_q.start(q[-1:], ctl, +1, sel=sel_q),
                    sx_u.start(u[-1:], ctl, +1))

        if wire is not None:
            return jax.jit(shard_map(
                prime_container_s, mesh=mesh,
                in_specs=(P("model", dp), P("model", dp), P(), P()),
                out_specs=((_payload_spec(wire, dp), hdr_spec),
                           (_payload_spec(FP32, dp), hdr_spec)),
                check_vma=False))
        return jax.jit(shard_map(
            prime_s, mesh=mesh,
            in_specs=(P("model", dp), P("model", dp), P()),
            out_specs=((_payload_spec(q_codec, dp), hdr_spec),
                       (_payload_spec(FP32, dp), hdr_spec)),
            check_vma=False))

    def prime(q, u):
        return (ex_q.start_shift_from_prev(q), ex_u.start_shift_from_prev(u))

    def prime_container(q, u, widths):
        sel_q = widths[0, jax.lax.axis_index("model")]
        return (cex.start_shift_from_prev(q, sel_q),
                ex_u.start_shift_from_prev(u))

    if wire is not None:
        return jax.jit(shard_map(
            prime_container, mesh=mesh,
            in_specs=(P("model", dp), P("model", dp), P()),
            out_specs=(_payload_spec(wire, dp), _payload_spec(FP32, dp)),
            check_vma=False))
    return jax.jit(shard_map(
        prime, mesh=mesh,
        in_specs=(P("model", dp), P("model", dp)),
        out_specs=(_payload_spec(q_codec, dp), _payload_spec(FP32, dp)),
        check_vma=False))


def make_sentinel_primer(mesh: Mesh, p_codec: WireCodec = FP32,
                         q_codec: WireCodec = FP32, *,
                         wire: Optional[PaddedWire] = None):
    """Initial :class:`~repro.comm.faults.GoodSlabs` for a sentinel step:
    ``prime(q, u, p) -> GoodSlabs`` (``prime(q, u, p, widths)`` with a
    padded-container `wire`). Each slab is produced by a CLEAN codec-
    faithful ring shift — exactly the boundary a fault-free tick would
    decode — so a fault on the very first tick already substitutes the
    right value."""
    dp = _dp_axes(mesh)
    ex_q = NeighborExchange("model", q_codec)
    ex_u = NeighborExchange("model", FP32)
    ex_p = NeighborExchange("model", p_codec)
    cex = None if wire is None else ContainerExchange("model", wire)
    n_stages = mesh.shape["model"]

    def prime(q, u, p):
        return FT.GoodSlabs(
            q=ex_q.shift_from_prev(q)[:1],
            u=ex_u.shift_from_prev(u)[:1],
            p=ex_p.shift_from_next(p)[-1:])

    def prime_container(q, u, p, widths):
        sidx = jax.lax.axis_index("model")
        sel_q = widths[0, sidx]
        sel_q_prev = widths[0, jnp.mod(sidx - 1, n_stages)]
        sel_p = widths[1, sidx]
        sel_p_next = widths[1, jnp.mod(sidx + 1, n_stages)]
        return FT.GoodSlabs(
            q=cex.shift_from_prev(q, sel_q, sel_q_prev)[:1],
            u=ex_u.shift_from_prev(u)[:1],
            p=cex.shift_from_next(p, sel_p, sel_p_next)[-1:])

    gspec = FT.GoodSlabs(P("model", dp), P("model", dp), P("model", dp))
    if wire is not None:
        return jax.jit(shard_map(
            prime_container, mesh=mesh,
            in_specs=(P("model", dp),) * 3 + (P(),),
            out_specs=gspec, check_vma=False))
    return jax.jit(shard_map(
        prime, mesh=mesh,
        in_specs=(P("model", dp),) * 3,
        out_specs=gspec, check_vma=False))


def shard_rows(V: int, dp_total: int) -> tuple:
    """Per-data-shard row counts of a length-V axis split `dp_total` ways,
    under JAX's ceil-partition of uneven axes (shard i holds rows
    [i*ceil(V/n), (i+1)*ceil(V/n)) clipped to V — trailing shards may be
    short or empty). Sums to V exactly for every (V, n)."""
    c = -(-V // dp_total)
    return tuple(max(0, min(V, (i + 1) * c) - i * c) for i in range(dp_total))


def wire_bytes_per_iteration(mesh, L: int, V: int, h: int,
                             p_codec: WireCodec, q_codec: WireCodec) -> dict:
    """Exact global bytes one distributed iteration puts on the stage ring:
    every stage sends its boundary slab [1, rows_i, h] per data shard — q
    and u forward, p backward. Ragged V (real-graph node counts that don't
    divide the data mesh) is accounted per shard: each shard's slab is
    charged at its own `codec.payload_bytes`, so remainder rows are never
    dropped and per-shard container rounding (int4 packing) is exact."""
    n_stages = mesh.shape["model"]
    assert L % n_stages == 0, (L, n_stages)
    dp_total = 1
    for a in ("pod", "data"):
        dp_total *= mesh.shape.get(a, 1)
    rows = shard_rows(V, dp_total)

    def edge_bytes(codec):
        return n_stages * sum(codec.payload_bytes((1, r, h)) for r in rows)

    return {
        "q_fwd": edge_bytes(q_codec),
        "u_fwd": edge_bytes(FP32),
        "p_bwd": edge_bytes(p_codec),
        "elements_per_edge": n_stages * V * h,   # == n_stages * sum(rows) * h
        "shard_rows": rows,
        "links": n_stages * dp_total,
    }


def container_wire_bytes_per_iteration(mesh, L: int, V: int, h: int,
                                       wire: PaddedWire, q_bits, p_bits
                                       ) -> dict:
    """Exact global bytes one padded-container iteration puts on the stage
    ring, split physical-vs-logical: every stage sends its q/p boundary slab
    as a fixed-capacity container (`wire` bytes — what the link carries),
    with the active codec's packed size as the logical payload (`q_fwd` /
    `p_bwd`, per stage). u still flies fp32. Ragged V accounted per data
    shard, exactly like :func:`wire_bytes_per_iteration`."""
    n_stages = mesh.shape["model"]
    assert len(q_bits) == len(p_bits) == n_stages
    dp_total = 1
    for a in ("pod", "data"):
        dp_total *= mesh.shape.get(a, 1)
    rows = shard_rows(V, dp_total)
    cap = sum(wire.capacity((1, r, h)) for r in rows)
    return {
        "q_fwd": [sum(wire.payload_bytes((1, r, h), b) for r in rows)
                  for b in q_bits],
        "p_bwd": [sum(wire.payload_bytes((1, r, h), b) for r in rows)
                  for b in p_bits],
        "u_fwd": n_stages * sum(FP32.payload_bytes((1, r, h)) for r in rows),
        "container_bytes": cap,              # physical, per stage, q or p
        "elements_per_edge": n_stages * V * h,
        "shard_rows": rows,
        "links": n_stages * dp_total,
    }


def _record_container_iteration(ledger, iteration: int, mesh, L, V, h,
                                wire: PaddedWire, q_bits, p_bits) -> None:
    """One padded-container iteration on the ledger: per stage, the q/p
    containers at their ACTIVE bit-width (logical payload) and fixed
    capacity (physical wire bytes); u as one fp32 record."""
    wb = container_wire_bytes_per_iteration(mesh, L, V, h, wire, q_bits,
                                            p_bits)
    n_el = V * h
    for i in range(mesh.shape["model"]):
        ledger.record(iteration, f"q_fwd/s{i}", "ppermute", n_el,
                      int(q_bits[i]), wb["q_fwd"][i],
                      wire_bytes=wb["container_bytes"])
        ledger.record(iteration, f"p_bwd/s{i}", "ppermute", n_el,
                      int(p_bits[i]), wb["p_bwd"][i],
                      wire_bytes=wb["container_bytes"])
    ledger.record(iteration, "u_fwd", "ppermute", wb["elements_per_edge"],
                  32, wb["u_fwd"])


def _record_container_qu_pair(ledger, iteration: int, mesh, L, V, h,
                              wire: PaddedWire, q_bits, suffix: str) -> None:
    """Charge one unconsumed q+u in-flight pair of the container path
    (``/inflight`` tail or ``/dropped`` on a q-schedule change)."""
    wb = container_wire_bytes_per_iteration(mesh, L, V, h, wire, q_bits,
                                            q_bits)
    n_stages = mesh.shape["model"]
    ledger.record(iteration, "q_fwd/" + suffix, "ppermute",
                  wb["elements_per_edge"], int(max(q_bits)),
                  sum(wb["q_fwd"]),
                  wire_bytes=n_stages * wb["container_bytes"])
    ledger.record(iteration, "u_fwd/" + suffix, "ppermute",
                  wb["elements_per_edge"], 32, wb["u_fwd"])


def _record_ring_span(ledger, start: int, n: int, mesh, L, V, h,
                      p_codec: WireCodec, q_codec: WireCodec) -> None:
    """Record `n` iterations of ring traffic (q/u forward, p backward) in
    one shot — the chunked driver's per-chunk rollup."""
    wb = wire_bytes_per_iteration(mesh, L, V, h, p_codec, q_codec)
    n_el = wb["elements_per_edge"]
    ledger.record_span(start, n, "q_fwd", "ppermute", n_el, q_codec.bits,
                       wb["q_fwd"])
    ledger.record_span(start, n, "u_fwd", "ppermute", n_el, 32, wb["u_fwd"])
    ledger.record_span(start, n, "p_bwd", "ppermute", n_el, p_codec.bits,
                       wb["p_bwd"])


def _record_qu_pair(ledger, iteration: int, mesh, L, V, h,
                    p_codec: WireCodec, q_codec: WireCodec,
                    suffix: str) -> None:
    """Charge one q+u forward slab pair that crossed the link outside the
    consumed per-iteration traffic: the in-flight tail a finished overlap
    run leaves in its carry (``/inflight``) or slabs superseded by a
    schedule change (``/dropped``). Bytes on the wire are bytes on the
    ledger, consumed or not."""
    wb = wire_bytes_per_iteration(mesh, L, V, h, p_codec, q_codec)
    n_el = wb["elements_per_edge"]
    ledger.record(iteration, "q_fwd/" + suffix, "ppermute", n_el,
                  q_codec.bits, wb["q_fwd"])
    ledger.record(iteration, "u_fwd/" + suffix, "ppermute", n_el, 32,
                  wb["u_fwd"])


def _sentinel_links(mesh) -> int:
    """Sentinel-checked links per edge per iteration: one slab per stage
    per data-parallel ring."""
    links = mesh.shape["model"]
    for a in ("pod", "data"):
        links *= mesh.shape.get(a, 1)
    return links


def _record_sentinel_headers(ledger, start: int, n: int, mesh,
                             edges=FT.EDGES) -> None:
    """Charge the integrity headers a sentinel step flies: int32[2] per
    slab per link per edge, physical ``wire_bytes`` only (kind ``header``,
    zero logical payload — excluded from the fp32 baseline like
    handshakes; integrity overhead is not part of the compression story)."""
    links = _sentinel_links(mesh)
    for edge in edges:
        ledger.record_span(start, n, edge, "header", 2 * links, 32,
                           payload_bytes=0,
                           wire_bytes=FT.SENTINEL_HEADER_BYTES * links)


# ---------------------------------------------------------------------------
# Replay cost-model hooks: trace a step variant into the analysis DAG and
# price schedules / the overlap knob against predicted wall time. These live
# HERE (not in analysis) because they know how the compiled steps are built;
# `make_distributed_step`'s signature is pinned by the observability tests,
# so everything goes through these helpers instead of new step kwargs.
# ---------------------------------------------------------------------------

class StepProgramPlan(NamedTuple):
    """The traced-program shape one `make_distributed_step` configuration
    commits to — the declarative half of the program-contract linter
    (:mod:`repro.analysis.contracts`), computed HERE so the invariants live
    next to the step builder that owns them rather than being
    reverse-engineered in tests.

      * `edge_events` — every expected ppermute in ISSUE ORDER, as
        ``(edge, wire_dtype, bytes_per_link)``. Sentinel steps interleave an
        ``<edge>.header`` event (int32[2], 8 B) after each payload; the
        per-link payload bytes come straight from ``codec.payload_bytes`` /
        ``PaddedWire.capacity`` on the boundary slab, so a traced ppermute
        whose operand disagrees is an undercounting wire.
      * `n_carried` — in-flight slabs leaving through the carry (2 under
        overlap: the double-buffered q/u forward exchange; else 0).
      * `min_work_to_consumer` — solver-shaped eqns REQUIRED between each
        consumed collective and its first reader (overlap puts the whole
        W/b/z solve family behind the p exchange; 0 demands the fused
        issue-where-consumed baseline ordering *exactly*).
      * `pallas_calls` — exact per-kernel dispatch counts (base body names,
        vmap ``_batched`` suffix normalized away) under the CURRENT
        ``REPRO_KERNELS`` policy; empty when the policy or
        ``config.use_kernels`` routes to the jnp oracles.
      * `fista_operand` — the padded [rows, cols] of the z and a operands of
        every fused FISTA step: one data shard's rows by the C logit
        columns, [pad(V_loc), pad(C)] (the z_L solve covers one layer and
        only its logits); None when the kernels are off.
      * `expects_xor` / `donate` / `takes_widths` / `sentinel` / `overlap`
        — presence flags for the fault injector's xor machinery, donation
        markers, the trailing widths table, headers, and the carried
        exchange.
    """
    edge_events: tuple
    n_carried: int
    min_work_to_consumer: int
    pallas_calls: dict
    fista_operand: Optional[Tuple[int, int]]
    expects_xor: bool
    donate: bool
    takes_widths: bool
    sentinel: bool
    overlap: bool


def _codec_wire_format(codec, slab):
    """(wire dtype, per-link bytes) of one boundary slab under `codec`."""
    if codec.bits >= 32:
        return "float32", codec.payload_bytes(slab)
    dtype = "uint8" if codec.bits <= 8 else "uint16"
    return dtype, codec.payload_bytes(slab)


def step_program_plan(mesh, L: int, n_classes: int, config: ADMMConfig, *,
                      V: int, h: int, overlap: bool = False,
                      donate: bool = False,
                      p_codec: Optional[WireCodec] = None,
                      q_codec: Optional[WireCodec] = None,
                      wire: Optional[PaddedWire] = None,
                      health: bool = False,
                      faults: Optional[FT.FaultPlan] = None
                      ) -> StepProgramPlan:
    """Expected program shape for this `make_distributed_step` kwarg point
    (same signature plus the ``V``/``h`` problem size). Pure bookkeeping —
    nothing is traced."""
    from repro.kernels import ops
    n_rows = 1
    for a in ("pod", "data"):
        n_rows *= mesh.shape.get(a, 1)
    r0 = shard_rows(V, n_rows)[0]
    slab = (1, r0, h)
    if p_codec is None:
        p_codec = codec_for_grid(config.grid if config.quantize_p else None)
    if q_codec is None:
        q_codec = codec_for_grid(config.grid if config.quantize_q else None)
    sentinel = bool(health) or faults is not None

    if wire is not None:
        q_fmt = p_fmt = ("uint8", wire.capacity(slab))
    else:
        q_fmt = _codec_wire_format(q_codec, slab)
        p_fmt = _codec_wire_format(p_codec, slab)
    u_fmt = ("float32", FP32.payload_bytes(slab))
    fmt = {"q_fwd": q_fmt, "u_fwd": u_fmt, "p_bwd": p_fmt}
    # issue order: the overlap body ISSUES p mid-body and q/u at the tail
    # (the entry exchange is a carry decode, not a collective)
    order = ("p_bwd", "q_fwd", "u_fwd") if overlap \
        else ("q_fwd", "u_fwd", "p_bwd")
    events = []
    for edge in order:
        dtype, nbytes = fmt[edge]
        events.append((edge, dtype, nbytes))
        if sentinel:
            events.append((edge + ".header", "int32",
                           FT.SENTINEL_HEADER_BYTES))

    if config.use_kernels and ops.kernels_enabled():
        pallas = {
            ops.KERNEL_NAMES["fused_linear"]: 3,       # residual + p + W
            ops.KERNEL_NAMES["admm_pgrad"]: 1,
            ops.KERNEL_NAMES["relu_zupdate"]: 1,
            ops.KERNEL_NAMES["fista_zlast"]: config.fista_iters + 1,
        }
        if config.quantize_p and config.grid is not None:
            # backtracking p-solve: the while-loop resnorm body traces once
            pallas[ops.KERNEL_NAMES["backtrack_resnorm"]] = 1
        if wire is not None:
            # every non-identity width packs+unpacks both container edges
            # (q and p) — lax.switch traces ALL branches
            for b in wire.widths:
                names = ops.pack_kernel_names(b)
                if names is not None:
                    for name in names:
                        pallas[name] = pallas.get(name, 0) + 2
        fista_operand = ops.padded_shape("fista_zlast", (r0, n_classes))
    else:
        pallas = {}
        fista_operand = None

    return StepProgramPlan(
        edge_events=tuple(events),
        n_carried=2 if overlap else 0,
        min_work_to_consumer=2 if overlap else 0,
        pallas_calls=pallas,
        fista_operand=fista_operand,
        expects_xor=faults is not None,
        donate=donate,
        takes_widths=wire is not None,
        sentinel=sentinel,
        overlap=overlap)


def trace_step_dag(mesh, L: int, n_classes: int, config: ADMMConfig, *,
                   V: int, h: int, overlap: bool = False,
                   p_codec: Optional[WireCodec] = None,
                   q_codec: Optional[WireCodec] = None,
                   wire: Optional[PaddedWire] = None):
    """Abstractly trace one compiled-step variant into the replay task DAG
    (:func:`repro.analysis.replay.extract_step_dag`) — nothing compiles and
    no device arrays are built (`jax.ShapeDtypeStruct` in, jaxpr out).

    The ppermute events are labeled with their CommLedger edge names in the
    order each variant issues them: the baseline body exchanges q/u at entry
    and p mid-body, the overlap body only ISSUES p mid-body and q/u at the
    tail (the entry exchange is a decode of the carry, not a collective)."""
    from repro.analysis import replay as rp
    n_stages = mesh.shape["model"]
    n_rows = 1
    for a in ("pod", "data"):
        n_rows *= mesh.shape.get(a, 1)
    step, _ = make_distributed_step(mesh, L, n_classes, config,
                                    overlap=overlap, p_codec=p_codec,
                                    q_codec=q_codec, wire=wire)
    f32, i32 = jnp.float32, jnp.int32
    sds = jax.ShapeDtypeStruct
    st = StackState(p=sds((L, V, h), f32), W=sds((L, h, h), f32),
                    b=sds((L, h), f32), z=sds((L, V, h), f32),
                    q=sds((L, V, h), f32), u=sds((L, V, h), f32))
    args = [sds((V, h), f32), sds((V,), i32), sds((V,), f32)]
    if wire is not None:
        args.append(sds((2, n_stages), i32))
    if overlap:
        qc = q_codec if q_codec is not None else codec_for_grid(
            config.grid if config.quantize_q else None)
        primer = make_overlap_primer(mesh, qc, wire=wire)
        pargs = (st.q, st.u) + ((args[-1],) if wire is not None else ())
        inflight = jax.eval_shape(primer, *pargs)
        carry = (st, inflight)
        names = ["p_bwd", "q_fwd", "u_fwd"]
    else:
        carry = st
        names = ["q_fwd", "u_fwd", "p_bwd"]
    jx = jax.make_jaxpr(step)(carry, *args)
    return rp.extract_step_dag(jx, n_stages=n_stages, n_rows=n_rows,
                               edge_names=names)


def choose_overlap_for(mesh, L: int, n_classes: int, config: ADMMConfig, *,
                       V: int, h: int, costs=None, n_workers=None) -> bool:
    """Replay-search the `overlap` knob for this training setup: trace both
    step variants and keep the predicted-faster schedule
    (:func:`repro.analysis.replay.choose_overlap`). With no cost table the
    hand default (overlap on — the PR-4 result) comes back without tracing
    anything."""
    from repro.analysis import replay as rp
    if costs is None:
        return rp.choose_overlap(None, None, None)
    kw = dict(V=V, h=h)
    return rp.choose_overlap(
        trace_step_dag(mesh, L, n_classes, config, overlap=False, **kw),
        trace_step_dag(mesh, L, n_classes, config, overlap=True, **kw),
        costs, n_workers=n_workers)


def step_cost_model(mesh, L: int, n_classes: int, config: ADMMConfig,
                    costs, *, V: int, h: int, grids_by_bits,
                    mixed_width: bool = True, overlap: bool = False,
                    n_workers=None):
    """Build the :class:`repro.analysis.replay.ScheduleCostModel` pricing
    THIS training setup's compiled step — the `cost_model` a
    ``BitWidthController(objective="walltime")`` consumes.

    ``mixed_width=True`` prices the padded-container step
    (``distributed_train(mixed_width=True)``): the physical ppermute payload
    is the fixed container capacity whatever the schedule says, so promoting
    an edge's precision is free in predicted time — the walltime objective
    then spends the whole container. ``mixed_width=False`` prices the
    uniform-codec adaptive path (one managed edge, ``schedule == (bits,)``):
    the packed payload grows with the scheduled width, so a promotion is
    accepted exactly when the replay predicts the extra transfer stays
    hidden under solver compute (on a bandwidth-starved link the bytes
    floor survives; on this ring the slabs are small and it rarely does)."""
    from repro.analysis import replay as rp
    n_rows = 1
    for a in ("pod", "data"):
        n_rows *= mesh.shape.get(a, 1)
    r0 = shard_rows(V, n_rows)[0]
    slab = (1, r0, h)
    u_bytes = FP32.payload_bytes(slab)
    if mixed_width:
        wire = PaddedWire.from_grids(grids_by_bits)
        dag = trace_step_dag(mesh, L, n_classes, config, V=V, h=h,
                             overlap=overlap, wire=wire)
        cap = wire.capacity(slab)
        fixed = {"q_fwd": cap, "p_bwd": cap, "u_fwd": u_bytes}
        edge_bytes = lambda schedule: fixed
    else:
        # DAG structure is width-independent on the codec path (only the
        # packed payload size moves) — trace once, reprice per schedule
        dag = trace_step_dag(mesh, L, n_classes, config, V=V, h=h,
                             overlap=overlap)

        def edge_bytes(schedule):
            codec = codec_for_grid(grids_by_bits[schedule[0]])
            b = codec.payload_bytes(slab)
            return {"q_fwd": b, "p_bwd": b, "u_fwd": u_bytes}
    return rp.ScheduleCostModel(dag, costs, edge_bytes, n_workers=n_workers)


_UNSET = object()


def _ft_train_loop(*, mesh, state, specs, data, L, V, h, n_classes, config,
                   epochs, hist, ledger, controller, codecs_for, step_cache,
                   overlap, faults, ckpt, ckpt_every, resume, recovery):
    """The sentinel training loop behind ``distributed_train(faults=/
    health=/ckpt=)``: per-iteration Python driver running ``health=True``
    steps, with last-good substitution compiled in, host-side fault
    accounting, checkpointing, and rollback recovery. Returns
    ``(state, hist)``; see the `distributed_train` docstring for the
    policy."""
    from repro.ckpt.manager import CheckpointManager
    Xp_s, lab, msk = data
    mgr = None
    if ckpt is not None:
        mgr = ckpt if hasattr(ckpt, "save") else CheckpointManager(str(ckpt))
    rec = recovery if recovery is not None else FT.RecoveryConfig()
    n_stages = mesh.shape["model"]
    links = _sentinel_links(mesh)
    dp_total = links // n_stages
    shardings = jax.tree.map(lambda sp: NamedSharding(mesh, sp), specs)

    def ft_step(bits):
        k = ("sentinel", bits)
        if k not in step_cache:
            pc, qc = codecs_for(bits)
            step_cache[k] = make_distributed_step(
                mesh, L, n_classes, config, overlap=overlap,
                p_codec=pc, q_codec=qc, health=True, faults=faults)[0]
        return step_cache[k]

    good_primers, fly_primers = {}, {}

    def prime_good(bits, st):
        if bits not in good_primers:
            pc, qc = codecs_for(bits)
            good_primers[bits] = make_sentinel_primer(mesh, pc, qc)
        return good_primers[bits](st.q, st.u, st.p)

    def prime_fly(bits, st, seqno):
        if bits not in fly_primers:
            fly_primers[bits] = make_overlap_primer(
                mesh, codecs_for(bits)[1], sentinel=True)
        return fly_primers[bits](st.q, st.u, jnp.asarray(seqno, jnp.int32))

    def charge_pair(it, old_bits, suffix):
        # a q/u pair (and its headers) that crossed the link un-consumed
        _record_qu_pair(ledger, it, mesh, L, V, h, *codecs_for(old_bits),
                        suffix)
        for en in ("q_fwd/", "u_fwd/"):
            ledger.record(it, en + suffix, "header", 2 * links, 32,
                          payload_bytes=0,
                          wire_bytes=FT.SENTINEL_HEADER_BYTES * links)

    state0 = state
    ctl_state0 = controller.state_dict() if controller is not None else None
    fault_counts = {en: {"injected": 0, "detected": 0, "recovered": 0}
                    for en in FT.EDGES}
    ft_trace = []
    n_rb = 0
    e, tick = 0, 0
    prev_obj = float("inf")
    stage_res = 0.0
    good, inflight, cur_bits = None, None, _UNSET

    def _restore_latest(with_tick: bool):
        nonlocal state, e, tick, prev_obj, stage_res
        state, manifest = mgr.restore(like=state, shardings=shardings)
        ex = manifest.get("extra") or {}
        e = int(ex.get("iteration", 0))
        prev_obj = float(ex.get("prev_obj", float("inf")))
        stage_res = float(ex.get("residual", 0.0))
        if with_tick:
            # cross-process resume continues the plan clock; an in-run
            # rollback NEVER rewinds it (transient wire events)
            tick = int(ex.get("tick", tick))
        if controller is not None and ex.get("controller"):
            controller.load_state_dict(ex["controller"])
        del hist["objective"][e:]
        del hist["residual"][e:]

    if resume and mgr is not None and mgr.latest_step() is not None:
        _restore_latest(with_tick=True)

    def _save():
        extra = {"iteration": e, "tick": tick, "prev_obj": prev_obj,
                 "residual": stage_res,
                 "controller": (controller.state_dict()
                                if controller is not None else None)}
        if ledger is not None:
            extra["ledger"] = ledger.summary()
        mgr.save(e, state, extra=extra)

    while e < epochs:
        if controller is not None:
            (bits,) = controller.assign([stage_res], e)
            hist["schedules"].append(bits)
        else:
            bits = None
        step = ft_step(bits)
        p_codec, q_codec = codecs_for(bits)
        if good is None or bits != cur_bits:
            if overlap and inflight is not None and ledger is not None:
                charge_pair(e, cur_bits, "dropped")
            good = prime_good(bits, state)
            inflight = prime_fly(bits, state, tick - 1) if overlap else None
            cur_bits = bits
        ctl = (faults.controls(tick, n_stages, prev_obj=prev_obj)
               if faults is not None
               else FT.null_controls(n_stages, seqno=tick,
                                     prev_obj=prev_obj))
        carry = (((state, good), inflight) if overlap else (state, good))
        out, m = step(carry, Xp_s, lab, msk, ctl)
        if overlap:
            (new_state, new_good), new_inflight = out
        else:
            (new_state, new_good), new_inflight = out, None
        hlth = jax.device_get(m["health"])
        wire_bad = [int(x) for x in hlth["wire_bad"]]
        healthy = (all(bool(hlth[k]) for k in
                       ("p_finite", "W_finite", "b_finite", "z_finite",
                        "residual_finite"))
                   and not bool(hlth["objective_spike"]))
        # -- fault accounting (every attempt, healthy or not) --------------
        if faults is not None:
            for (en, s_, kind) in faults.events(tick, n_stages):
                ft_trace.append((tick, en, int(s_), kind))
                # one event corrupts that link's slab on EVERY dp ring
                fault_counts[en]["injected"] += dp_total
                if ledger is not None:
                    ledger.record_fault(tick, en, "injected", dp_total,
                                        detail=kind)
        for en, bad in zip(FT.EDGES, wire_bad):
            if bad:
                # every failed verdict substituted last-good in-step
                fault_counts[en]["detected"] += bad
                fault_counts[en]["recovered"] += bad
                if ledger is not None:
                    ledger.record_fault(tick, en, "detected", bad)
                    ledger.record_fault(tick, en, "recovered", bad)
        if ledger is not None:
            # the attempt's bytes moved whether or not it is accepted
            _record_ring_span(ledger, e, 1, mesh, L, V, h, p_codec, q_codec)
            _record_sentinel_headers(ledger, e, 1, mesh)
        tick += 1
        if healthy:
            state, good, inflight = new_state, new_good, new_inflight
            prev_obj = float(m["objective"])
            stage_res = float(m["residual"])
            hist["objective"].append(prev_obj)
            hist["residual"].append(stage_res)
            e += 1
            if mgr is not None and ckpt_every and e % ckpt_every == 0:
                _save()
        else:
            n_rb += 1
            if ledger is not None:
                ledger.record_fault(tick - 1, "step", "rolled_back", 1)
            if n_rb > rec.max_rollbacks:
                raise RuntimeError(
                    f"distributed_train: {n_rb} rollbacks exceeded "
                    f"max_rollbacks={rec.max_rollbacks} — persistent "
                    "divergence, not transient faults")
            if overlap and ledger is not None:
                # the failed attempt's carry pair is discarded
                charge_pair(e, cur_bits, "dropped")
            if mgr is not None and mgr.latest_step() is not None:
                _restore_latest(with_tick=False)
            else:
                state = state0
                e = 0
                prev_obj = float("inf")
                stage_res = 0.0
                del hist["objective"][:]
                del hist["residual"][:]
                if controller is not None and ctl_state0 is not None:
                    controller.load_state_dict(ctl_state0)
            if controller is not None:
                controller.force_widest(e, rec.cooldown)
            good, inflight, cur_bits = None, None, _UNSET

    if overlap and ledger is not None and cur_bits is not _UNSET:
        # the tail pair still in flight in the carry at termination
        charge_pair(epochs, cur_bits, "inflight")
    hist["faults"] = {
        "per_edge": fault_counts,
        "injected": sum(c["injected"] for c in fault_counts.values()),
        "detected": sum(c["detected"] for c in fault_counts.values()),
        "recovered": sum(c["recovered"] for c in fault_counts.values()),
        "rolled_back": n_rb,
        "ticks": tick,
        "trace": ft_trace,
    }
    return state, hist


def distributed_train(mesh, key, Xp, labels, masks, L, n_classes,
                      config: ADMMConfig, epochs: int, *, ledger=None,
                      controller=None, grids_by_bits=None,
                      overlap=False, chunk: int = 32,
                      mixed_width: bool = False, cost_table=None,
                      faults: Optional[FT.FaultPlan] = None,
                      health: bool = False, ckpt=None,
                      ckpt_every: int = 0, resume: bool = False,
                      recovery: Optional[FT.RecoveryConfig] = None):
    """End-to-end stage-parallel training loop (small meshes / tests).

    The no-controller path rides a chunked ``lax.scan`` driver
    (``pdadmm.run_chunked``): metrics stay on device inside each chunk, so
    the host syncs once per `chunk` iterations instead of every epoch. With
    ``overlap=True`` the double-buffered boundary exchange's in-flight
    encoded slabs are part of the scan carry (primed once before the loop);
    results are bitwise-identical to ``overlap=False``.

    With a `ledger`, every iteration's ring traffic is recorded edge-by-edge
    (whole chunks at a time on the scan path). With a `controller`
    (+ `grids_by_bits`), the p/q wire bit-width is chosen each epoch from
    the global primal residual; SPMD keeps one wire format per compiled
    step, so schedule changes swap between cached compilations — built
    LAZILY, so only schedules that actually run compile (observable as
    ``hist["n_compiled_steps"]``). A schedule change under overlap re-primes
    the carry with the new wire format.

    ``mixed_width=True`` (requires `controller` + `grids_by_bits`) rides the
    padded-container wire instead: ONE step compiles
    (``hist["n_compiled_steps"] == 1``) and the controller assigns each ring
    boundary its own bit-width every iteration from the per-stage primal
    residuals (``metrics["stage_residuals"]``), passed into the compiled
    step as a traced widths table — schedule changes never recompile. The
    controller manages ``n_stages`` edges (one width per boundary, q and p
    shared) or ``2 * n_stages`` (q edges then p edges). The ledger records
    each stage's container at its active width: logical `payload_bytes` =
    the packed active codec, physical `wire_bytes` = the fixed container
    capacity.

    Overlap ledger accounting: the N consumed per-iteration exchanges are
    recorded identically to ``overlap=False`` (overlap changes when bytes
    move, not how many an iteration consumes), and every in-flight slab
    pair that crossed the link WITHOUT being consumed is charged explicitly
    — the tail pair a finished run leaves in its carry (``*/inflight`` at
    iteration `epochs`) and any pair superseded by a schedule change
    (``*/dropped``). Bytes on the wire are bytes on the ledger.

    ``overlap="replay"`` makes the knob a replay-searched choice: both step
    variants are traced and the predicted-faster one runs
    (:func:`choose_overlap_for`, priced by `cost_table` — a calibrated
    :class:`repro.analysis.costs.CostTable`; without one the hand default,
    overlap on, applies). The resolved value lands in ``hist["overlap"]``.

    Fault tolerance (any of `faults` / `health=True` / `ckpt`) switches to
    the SENTINEL loop: every iteration runs a ``health=True`` step (wire
    integrity headers + last-good substitution + finite/spike sentinels,
    see :mod:`repro.comm.faults`), `faults` injects its deterministic chaos
    schedule, and an UNHEALTHY iteration (non-finite state/metrics or an
    objective spike — what undetected corruption causes) is rolled back:
    restore the latest checkpoint (or the initial state when none exists),
    re-prime the good-slab and overlap carries, and
    :meth:`BitWidthController.force_widest` for ``recovery.cooldown``
    control steps. `ckpt` is a :class:`repro.ckpt.manager.CheckpointManager`
    or a directory path; ``ckpt_every=k`` saves atomically every k accepted
    iterations (ADMM state + iteration/objective + controller schedule
    state + ledger rollups in the manifest), ``resume=True`` restores the
    latest checkpoint before training — restore goes through the CURRENT
    mesh's shardings, so resuming onto a different mesh shape is elastic by
    construction. ``hist["faults"]`` accounts every injected event
    (re-enumerated from the plan) against detected/recovered wire verdicts
    and rollbacks; the ledger (if any) gains per-edge fault counters and
    the header wire bytes. The plan tick advances every ATTEMPTED
    iteration and is never rewound by a rollback — faults are transient
    wire events, so a replayed iteration does not re-suffer them.
    Incompatible with ``mixed_width=True`` for now.
    """
    V, h = Xp.shape
    if overlap == "replay":
        overlap = choose_overlap_for(mesh, L, n_classes, config, V=V, h=h,
                                     costs=cost_table)
    overlap = bool(overlap)
    state = init_stack(key, Xp, L, config)
    dp = _dp_axes(mesh)
    specs = stack_partition_specs(mesh)

    step_cache = {}

    def codecs_for(bits):
        if bits is None:
            return (codec_for_grid(config.grid if config.quantize_p
                                   else None),
                    codec_for_grid(config.grid if config.quantize_q
                                   else None))
        codec = codec_for_grid(grids_by_bits[bits])
        return codec, codec

    def step_for(bits):
        if bits not in step_cache:
            pc, qc = codecs_for(bits)
            step_cache[bits] = make_distributed_step(
                mesh, L, n_classes, config, overlap=overlap,
                p_codec=pc, q_codec=qc)[0]
        return step_cache[bits]

    primer_cache = {}

    def prime(bits, st):
        if bits not in primer_cache:
            primer_cache[bits] = make_overlap_primer(mesh,
                                                     codecs_for(bits)[1])
        return primer_cache[bits](st.q, st.u)

    put = lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec))
    state = jax.tree.map(lambda x, s: put(x, s), state, specs)
    Xp_s = put(Xp, P(dp))
    lab = put(labels, P(dp))
    msk = put(masks["train"], P(dp))
    hist = {"objective": [], "residual": [], "schedules": []}

    ft_mode = faults is not None or bool(health) or ckpt is not None
    if (resume or ckpt_every) and ckpt is None:
        raise ValueError("resume=/ckpt_every= need ckpt= (a "
                         "CheckpointManager or a directory path)")
    if ft_mode and mixed_width:
        raise NotImplementedError(
            "mixed_width is not supported with faults/health/ckpt yet — "
            "the fault-tolerant loop drives the uniform-codec step family")

    if ft_mode:
        state, hist = _ft_train_loop(
            mesh=mesh, state=state, specs=specs, data=(Xp_s, lab, msk),
            L=L, V=V, h=h, n_classes=n_classes, config=config,
            epochs=epochs, hist=hist, ledger=ledger, controller=controller,
            codecs_for=codecs_for, step_cache=step_cache, overlap=overlap,
            faults=faults, ckpt=ckpt, ckpt_every=ckpt_every, resume=resume,
            recovery=recovery)
    elif mixed_width:
        assert controller is not None and grids_by_bits is not None, \
            "mixed_width needs a controller and grids_by_bits"
        wire = PaddedWire.from_grids(grids_by_bits)
        n_stages = mesh.shape["model"]
        n_edges = len(controller.edge_elements)
        assert n_edges in (n_stages, 2 * n_stages), (n_edges, n_stages)
        step_cache["container"] = make_distributed_step(
            mesh, L, n_classes, config, overlap=overlap, wire=wire)[0]
        step = step_cache["container"]
        primer = (make_overlap_primer(mesh, wire=wire) if overlap else None)
        stage_res = [0.0] * n_stages
        inflight, prev_q_bits = None, None
        for e in range(epochs):
            sig = stage_res if n_edges == n_stages else stage_res + stage_res
            sched = controller.assign(sig, e)
            q_bits = sched[:n_stages]
            p_bits = sched[:n_stages] if n_edges == n_stages \
                else sched[n_stages:]
            hist["schedules"].append(sched)
            widths = jnp.stack([wire.sel_of_bits(q_bits),
                                wire.sel_of_bits(p_bits)])
            if overlap:
                if inflight is None or q_bits != prev_q_bits:
                    if inflight is not None and ledger is not None:
                        # the superseded in-flight pair (old q widths)
                        # already crossed the link — account for it
                        _record_container_qu_pair(ledger, e, mesh, L, V, h,
                                                  wire, prev_q_bits,
                                                  "dropped")
                    inflight = primer(state.q, state.u, widths)
                    prev_q_bits = q_bits
                (state, inflight), m = step((state, inflight), Xp_s, lab,
                                            msk, widths)
            else:
                state, m = step(state, Xp_s, lab, msk, widths)
            stage_res = [float(v) for v in m["stage_residuals"]]
            hist["objective"].append(float(m["objective"]))
            hist["residual"].append(float(m["residual"]))
            if ledger is not None:
                _record_container_iteration(ledger, e, mesh, L, V, h, wire,
                                            q_bits, p_bits)
        if overlap and ledger is not None and epochs > 0:
            # the tail pair still in flight in the carry at termination
            _record_container_qu_pair(ledger, epochs, mesh, L, V, h, wire,
                                      prev_q_bits, "inflight")
    elif controller is None:
        p_codec, q_codec = codecs_for(None)
        step = step_for(None)
        carry = (state, prime(None, state)) if overlap else state
        carry, ms = run_chunked(step, carry, (Xp_s, lab, msk), epochs,
                                chunk=chunk)
        state = carry[0] if overlap else carry
        hist["objective"] = [float(x) for x in ms.get("objective", ())]
        hist["residual"] = [float(x) for x in ms.get("residual", ())]
        if ledger is not None and epochs > 0:
            _record_ring_span(ledger, 0, epochs, mesh, L, V, h,
                              p_codec, q_codec)
            if overlap:   # the tail pair still in flight in the carry
                _record_qu_pair(ledger, epochs, mesh, L, V, h,
                                p_codec, q_codec, "inflight")
    else:
        residual = 0.0
        inflight, cur_bits = None, None
        for e in range(epochs):
            (bits,) = controller.assign([residual], e)
            hist["schedules"].append(bits)
            step = step_for(bits)
            p_codec, q_codec = codecs_for(bits)
            if overlap:
                if inflight is None or bits != cur_bits:
                    if inflight is not None and ledger is not None:
                        # superseded in-flight slabs (old wire format)
                        # already crossed the link — account for them
                        old_pc, old_qc = codecs_for(cur_bits)
                        _record_qu_pair(ledger, e, mesh, L, V, h,
                                        old_pc, old_qc, "dropped")
                    inflight = prime(bits, state)
                    cur_bits = bits
                (state, inflight), m = step((state, inflight), Xp_s, lab,
                                            msk)
            else:
                state, m = step(state, Xp_s, lab, msk)
            residual = float(m["residual"])
            hist["objective"].append(float(m["objective"]))
            hist["residual"].append(residual)
            if ledger is not None:
                _record_ring_span(ledger, e, 1, mesh, L, V, h,
                                  p_codec, q_codec)
        if overlap and ledger is not None and epochs > 0:
            # the tail pair still in flight in the carry at termination
            _record_qu_pair(ledger, epochs, mesh, L, V, h,
                            *codecs_for(cur_bits), "inflight")
    hist["n_compiled_steps"] = len(step_cache)
    hist["overlap"] = overlap
    return state, hist
