"""Trace-driven replay cost model: predict a stage-parallel step's wall time
offline, find its critical path, and search schedules against *time* instead
of bytes.

Lineage: byteprofile-analysis / dPRO replay a profiled training DAG over
per-device queues to predict step time and locate the critical path; AdaQP
frames message quantization as a wall-time problem, not a byte problem.
This is the jax-native equivalent: the DAG comes from the jitted step's
**jaxpr** (no profiler needed — the trace is the ground truth, walked with
the same :mod:`repro.analysis.jaxpr_tools` machinery the schedule tests
use), costs come from a measured :class:`~repro.analysis.costs.CostTable`,
and transfers are priced by the parametric link model ``time = latency +
wire_bytes / bandwidth`` fed by the SAME physical byte counts the
:class:`~repro.comm.ledger.CommLedger` charges.

Format — three layers:

  1. **DAG** (:func:`extract_step_dag` → :class:`StepDag`): the step body
     that holds the collectives (the ``shard_map`` body), cut into
     alternating :class:`Segment` compute tasks (dot_general flops,
     streamed elementwise bytes, pallas dispatches, per-eqn counts;
     ``cond`` charges its widest branch, ``while``/``scan`` multiply by
     trip count) and :class:`CommEvent` s (one per collective eqn, in
     program order) carrying the per-shard wire bytes straight off the
     traced aval — for a codec-formatted ppermute that IS the packed
     container the ledger charges. Each event is classified exactly like
     :func:`~repro.analysis.jaxpr_tools.collective_profile`: ``carried``
     (result leaves the body — consumed at the NEXT iteration's entry),
     hidden (consumed in-body with solver work between issue and use), or
     blocking (consumed immediately: it sits on the critical path).
     ``edge_names`` keys ppermute events by the CommLedger edge names
     (``q_fwd``/``u_fwd``/``p_bwd``), so ledger byte counts can be spliced
     in via :meth:`StepDag.with_wire_bytes`.

  2. **Costs**: a :class:`CostTable` (see its key conventions) prices
     compute segments (flops/bytes/per-eqn rates), blocking-collective
     rendezvous tolls, async issue tolls, and the link.

  3. **Replay** (:func:`replay`): a deterministic discrete-event simulation
     over per-device queues — ``n_rows × n_stages`` logical devices, each
     executing the DAG's task sequence in program order, compute contending
     for ``n_workers`` executor slots (the CPU device simulator runs many
     logical devices on few cores; on real hardware workers == devices),
     psums as global barriers, ppermutes as neighbor-edge messages whose
     arrival is ``sender issue end + link.transfer_time(wire_bytes)``.
     Returns steady-state step time (last-iteration window of a multi-
     iteration replay), per-stage busy/idle, and the critical path (the
     zero-slack chain, walked back through each task's determining
     predecessor). No wall clock anywhere — same inputs, same prediction.

Searches built on top: :func:`choose_psum_mode` (replay-priced gather vs
code-psum vs fp32 psum; falls back to the hand-derived ``world*bits < 64``
ring rule of :func:`repro.comm.transport.psum_mode` when no cost table is
given), :func:`choose_overlap` (replay both step variants, keep the faster
— the hand default is overlap on), and :class:`ScheduleCostModel` (per-
boundary bit-width schedule → predicted step seconds, the
``objective="walltime"`` hook of
:class:`repro.comm.controller.BitWidthController`).
"""
from __future__ import annotations

import dataclasses
import heapq
import math
import os
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

from repro.analysis.costs import CostTable, LinkModel
from repro.analysis.jaxpr_tools import jaxprs_with

COLLECTIVE_PRIMS = ("ppermute", "psum", "all_gather", "all_to_all",
                    "pmin", "pmax", "reduce_scatter")

WORK_PRIMS = ("dot_general", "pallas_call")


# ---------------------------------------------------------------------------
# DAG nodes
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Segment:
    """A run of compute eqns between two collectives (one replay task per
    device). Costs are aggregated, not per-eqn: dense-contraction flops,
    streamed output bytes of everything else, pallas dispatch count, and the
    raw eqn count (per-eqn overhead)."""
    index: int
    flops: float = 0.0
    bytes: float = 0.0
    n_pallas: int = 0
    n_eqns: int = 0

    def seconds(self, costs: CostTable) -> float:
        return (self.flops / costs.get("rate:dot_flops")
                + self.bytes / costs.get("rate:eltwise_bytes")
                + self.n_pallas * costs.get("op:pallas_call", 0.0)
                + self.n_eqns * costs.get("rate:op_overhead"))


@dataclasses.dataclass
class CommEvent:
    """One collective eqn of the step body, in program order."""
    index: int
    prim: str                    # "ppermute" | "psum" | ...
    dtype: str
    wire_bytes: int              # per-shard physical bytes (traced aval)
    carried: bool                # consumed only by the NEXT iteration
    work_to_consumer: int
    consumer_index: Optional[int]   # DAG index of the consuming Segment
    edge: Optional[str] = None      # CommLedger edge name, when known
    ring_delta: int = 1             # ppermute: receiver d gets from d-delta

    @property
    def blocking(self) -> bool:
        """Consumed in-body with no solver work between issue and use: the
        rendezvous sits on the critical path."""
        return (not self.carried) and self.work_to_consumer == 0


Item = Union[Segment, CommEvent]


@dataclasses.dataclass
class StepDag:
    """Program-ordered task template of ONE step, per device."""
    items: List[Item]
    n_stages: int
    n_rows: int = 1              # data-parallel replicas of the stage ring

    @property
    def comm_events(self) -> List[CommEvent]:
        return [x for x in self.items if isinstance(x, CommEvent)]

    @property
    def segments(self) -> List[Segment]:
        return [x for x in self.items if isinstance(x, Segment)]

    def counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for e in self.comm_events:
            out[e.prim] = out.get(e.prim, 0) + 1
        return out

    def with_wire_bytes(self, by_edge: Dict[str, int]) -> "StepDag":
        """New DAG with named ppermute edges re-priced from ledger-shaped
        per-shard byte counts (``WireRecord.wire_bytes`` divided down to one
        link) — the splice point between the CommLedger and the replay."""
        items: List[Item] = []
        for x in self.items:
            if isinstance(x, CommEvent) and x.edge in by_edge:
                x = dataclasses.replace(x, wire_bytes=int(by_edge[x.edge]))
            items.append(x)
        return StepDag(items, self.n_stages, self.n_rows)


def _dot_flops(eqn) -> float:
    """2*batch*M*N*K off the eqn's dimension numbers + operand avals."""
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    lhs, rhs = (v.aval.shape for v in eqn.invars[:2])
    batch = math.prod(lhs[d] for d in lb) if lb else 1
    k = math.prod(lhs[d] for d in lc) if lc else 1
    m = math.prod(d for i, d in enumerate(lhs) if i not in set(lc) | set(lb))
    n = math.prod(d for i, d in enumerate(rhs) if i not in set(rc) | set(rb))
    return 2.0 * batch * m * n * k


def _out_bytes(eqn) -> float:
    total = 0.0
    for v in eqn.outvars:
        aval = getattr(v, "aval", None)
        if aval is not None and hasattr(aval, "shape"):
            total += math.prod(aval.shape) * getattr(aval.dtype, "itemsize",
                                                     4)
    return total


def _trip_count(eqn) -> int:
    """Static trip count of a loop eqn (scan carries `length`; a while's
    trips are data-dependent — charge WHILE_TRIPS bodies)."""
    if eqn.primitive.name == "scan":
        return int(eqn.params.get("length", 1))
    return WHILE_TRIPS


WHILE_TRIPS = 3   # backtracking while-loops: typical accepted-trial count


def _accumulate(seg: Segment, jaxpr, mult: float = 1.0) -> None:
    """Fold a (sub)jaxpr's compute into `seg`. ``cond`` charges its single
    widest branch (a lax.switch runs ONE branch — summing them would bill
    every inactive wire width of a PaddedWire decode); loops multiply by
    trip count."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "cond":
            branches = [b.jaxpr for b in eqn.params["branches"]]
            probes = []
            for b in branches:
                p = Segment(-1)
                _accumulate(p, b, mult)
                probes.append(p)
            widest = max(probes, key=lambda p: (p.flops, p.bytes, p.n_eqns))
            seg.flops += widest.flops
            seg.bytes += widest.bytes
            seg.n_pallas += widest.n_pallas
            seg.n_eqns += widest.n_eqns
            continue
        subs = []
        for v in eqn.params.values():
            for x in (v if isinstance(v, (list, tuple)) else [v]):
                if hasattr(x, "jaxpr"):
                    subs.append(x.jaxpr)
                elif hasattr(x, "eqns"):
                    subs.append(x)
        if name in ("while", "scan") and subs:
            t = mult * _trip_count(eqn)
            for s in subs:
                _accumulate(seg, s, t)
            continue
        if name == "dot_general":
            seg.flops += mult * _dot_flops(eqn)
        elif name == "pallas_call":
            seg.n_pallas += int(round(mult))
            seg.bytes += mult * _out_bytes(eqn)
        else:
            seg.bytes += mult * _out_bytes(eqn)
        seg.n_eqns += int(round(mult))
        for s in subs:
            _accumulate(seg, s, mult)


def _ring_delta(eqn) -> int:
    """Receiver r of a ppermute gets from r - delta (mod ring)."""
    perm = eqn.params.get("perm", ())
    if perm:
        src, dst = perm[0]
        n = len(perm)
        return int((dst - src) % n) or 1
    return 1


def extract_step_dag(jaxpr, n_stages: int, *, n_rows: int = 1,
                     edge_names: Optional[Sequence[str]] = None,
                     work=WORK_PRIMS) -> StepDag:
    """Cut the step jaxpr into the alternating Segment/CommEvent task list.

    Walks into the (sub)jaxpr that holds the collectives DIRECTLY (the
    shard_map body — found with :func:`jaxprs_with`, preferring a ppermute
    body, falling back to psum, then to the whole jaxpr as one compute
    segment). ``edge_names`` labels the ppermute events, in program order,
    with their CommLedger edge names.
    """
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    body = None
    for prim in ("ppermute", "psum"):
        bodies = list(jaxprs_with(jaxpr, prim))
        if bodies:
            body = bodies[0]
            break
    if body is None:
        seg = Segment(0)
        _accumulate(seg, jaxpr)
        return StepDag([seg], n_stages, n_rows)

    # first pass: eqn index -> item index (collectives split the segments)
    is_coll = [e.primitive.name in COLLECTIVE_PRIMS for e in body.eqns]
    item_of_eqn: List[int] = []
    idx = 0
    pending_compute = False
    for flag in is_coll:
        if flag:
            if pending_compute:
                idx += 1                     # close the open segment
                pending_compute = False
            item_of_eqn.append(idx)
            idx += 1
        else:
            item_of_eqn.append(idx)
            pending_compute = True

    items: List[Item] = []
    seg: Optional[Segment] = None
    n_pp = 0
    work_set = tuple(work)
    for i, eqn in enumerate(body.eqns):
        name = eqn.primitive.name
        if name not in COLLECTIVE_PRIMS:
            if seg is None:
                seg = Segment(len(items))
                items.append(seg)
            _accumulate(seg, _single_eqn_view(eqn))
            continue
        seg = None
        v = eqn.outvars[0]
        consumers = [j for j in range(i + 1, len(body.eqns))
                     if any(iv is v for iv in body.eqns[j].invars)]
        between = 0
        if consumers:
            # count issue→use solver work the same way collective_profile does
            between = sum(_count_work(body.eqns[j], work_set)
                          for j in range(i + 1, consumers[0]))
        edge = None
        if name == "ppermute":
            if edge_names is not None and n_pp < len(edge_names):
                edge = edge_names[n_pp]
            n_pp += 1
        ev = CommEvent(
            index=len(items), prim=name, dtype=str(v.aval.dtype),
            wire_bytes=int(math.prod(v.aval.shape)
                           * getattr(v.aval.dtype, "itemsize", 4)),
            carried=not consumers,
            work_to_consumer=between,
            consumer_index=(item_of_eqn[consumers[0]] if consumers else None),
            edge=edge,
            ring_delta=_ring_delta(eqn) if name == "ppermute" else 0)
        items.append(ev)
    return StepDag(items, n_stages, n_rows)


def _count_work(eqn, work) -> int:
    from repro.analysis.jaxpr_tools import count_primitives
    n = 1 if eqn.primitive.name in work else 0
    for v in eqn.params.values():
        for x in (v if isinstance(v, (list, tuple)) else [v]):
            sub = getattr(x, "jaxpr", x if hasattr(x, "eqns") else None)
            if sub is not None:
                n += count_primitives(sub, work)
    return n


class _single_eqn_view:
    """Adapter: feed one eqn through `_accumulate` (which walks `.eqns`)."""
    def __init__(self, eqn):
        self.eqns = [eqn]


# ---------------------------------------------------------------------------
# Deterministic discrete-event replay over per-device queues
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ReplayResult:
    step_time_s: float
    total_time_s: float
    n_iterations: int
    per_stage_busy_s: List[float]     # compute seconds per stage, one step
    per_stage_idle_s: List[float]     # step_time - busy, per stage
    critical_path: List[Tuple[str, float]]   # (task label, duration)

    @property
    def step_time_ms(self) -> float:
        return self.step_time_s * 1e3

    def critical_comm(self) -> List[Tuple[str, float]]:
        """Comm tasks on the critical path, slowest first."""
        comm = [(lbl, d) for lbl, d in self.critical_path
                if not lbl.startswith("seg")]
        return sorted(comm, key=lambda t: -t[1])


def default_n_workers(n_devices: int) -> int:
    """Executor slots: real cores, capped at the device count (the CPU
    device simulator time-slices many logical devices onto few cores; on
    real accelerators every device computes concurrently)."""
    return max(1, min(os.cpu_count() or 1, n_devices))


def replay(dag: StepDag, costs: Optional[CostTable] = None, *,
           n_iterations: int = 4, n_workers: Optional[int] = None,
           link: Optional[LinkModel] = None) -> ReplayResult:
    """Deterministic DES of `n_iterations` steps of the DAG.

    Devices are the ``n_rows * n_stages`` mesh shards, each running the
    item list in program order. Compute segments contend for `n_workers`
    executor slots (priority: earliest-ready, then device id — fully
    deterministic). Blocking psums/all_gathers are global barriers of
    duration ``collective:<prim> + transfer``; blocking ppermutes are
    per-device neighbor syncs; carried/hidden collectives cost an issue
    toll at their program position and their transfer overlaps whatever
    compute follows, constraining only their consumer segment (next
    iteration's entry for carried events).
    """
    costs = costs or CostTable()
    link = link or costs.link
    D = dag.n_rows * dag.n_stages
    W = n_workers if n_workers is not None else default_n_workers(D)

    def stage_of(d):
        return d % dag.n_stages

    def ring(d, delta):
        row = d // dag.n_stages
        return row * dag.n_stages + (stage_of(d) - delta) % dag.n_stages

    seg_secs = {x.index: x.seconds(costs) for x in dag.segments}
    dispatch = costs.get("step:dispatch")

    # ---- build tasks -----------------------------------------------------
    # key: (iter, item_index, device) for per-device tasks;
    #      (iter, item_index, -1) for global barriers.
    tasks: Dict[Tuple[int, int, int], dict] = {}

    def add(key, label, duration, uses_slot, deps, device):
        tasks[key] = {"label": label, "dur": float(duration),
                      "slot": uses_slot, "deps": list(deps),
                      "device": device}

    first_item = dag.items[0].index if dag.items else 0
    for it in range(n_iterations):
        prev_of = {}        # device -> previous task key this iteration
        if it > 0:
            for d in range(D):
                prev_of[d] = last_of[d]                       # noqa: F821
        for x in dag.items:
            if isinstance(x, Segment):
                dur = seg_secs[x.index] + (dispatch if x.index == first_item
                                           else 0.0)
                for d in range(D):
                    deps = [(prev_of[d], 0.0)] if d in prev_of else []
                    add((it, x.index, d), f"seg{x.index}", dur, True, deps, d)
                    prev_of[d] = (it, x.index, d)
                continue
            lbl = x.edge or f"{x.prim}{x.index}"
            xfer = link.transfer_time(x.wire_bytes)
            if x.blocking and x.prim != "ppermute":
                # global barrier: everyone arrives, rendezvous toll + wire
                toll = costs.get(f"collective:{x.prim}")
                deps = [(prev_of[d], 0.0) for d in range(D) if d in prev_of]
                add((it, x.index, -1), lbl, toll + xfer, False, deps, -1)
                for d in range(D):
                    prev_of[d] = (it, x.index, -1)
                continue
            if x.blocking:
                # blocking ppermute: neighbor sync per device
                toll = costs.get("collective:ppermute")
                for d in range(D):
                    deps = [(prev_of[d], 0.0)] if d in prev_of else []
                    s = ring(d, x.ring_delta)
                    if s in prev_of:
                        deps.append((prev_of[s], 0.0))
                    add((it, x.index, d), lbl, toll + xfer, False, deps, d)
                for d in range(D):
                    prev_of[d] = (it, x.index, d)
                continue
            # hidden or carried: async issue at this point in the queue
            toll = costs.get(f"collective:{x.prim}:issue")
            for d in range(D):
                deps = [(prev_of[d], 0.0)] if d in prev_of else []
                add((it, x.index, d), f"{lbl}:issue", toll, False, deps, d)
                prev_of[d] = (it, x.index, d)
        last_of = dict(prev_of)

    # arrival constraints: the consumer segment waits for the message (for
    # carried events that is the NEXT iteration's entry task, so this runs
    # after every iteration's tasks exist)
    for it in range(n_iterations):
        for x in dag.items:
            if not isinstance(x, CommEvent) or x.blocking:
                continue
            cons_iter, cons_idx = it, x.consumer_index
            if x.carried:
                cons_iter, cons_idx = it + 1, first_item
            if cons_iter >= n_iterations or cons_idx is None:
                continue
            for d in range(D):
                src = ring(d, x.ring_delta) if x.prim == "ppermute" else None
                senders = range(D) if src is None else (src,)
                xfer = link.transfer_time(x.wire_bytes)
                key = (cons_iter, cons_idx, d)
                if key not in tasks:     # consumer is a barrier
                    key = (cons_iter, cons_idx, -1)
                for s in senders:
                    tasks[key]["deps"].append(((it, x.index, s), xfer))

    # ---- simulate --------------------------------------------------------
    n_deps = {k: len(t["deps"]) for k, t in tasks.items()}
    dependents: Dict[Tuple, List[Tuple]] = {k: [] for k in tasks}
    for k, t in tasks.items():
        for dep, _lag in t["deps"]:
            dependents[dep].append(k)
    end: Dict[Tuple, float] = {}
    det: Dict[Tuple, Optional[Tuple]] = {}
    ready_heap: List[Tuple[float, Tuple]] = []

    def ready_time(k):
        best, best_dep = 0.0, None
        for dep, lag in tasks[k]["deps"]:
            t = end[dep] + lag
            if t > best:
                best, best_dep = t, dep
        return best, best_dep

    for k, n in n_deps.items():
        if n == 0:
            heapq.heappush(ready_heap, (0.0, k))
            det[k] = None
    workers = [(0.0, None)] * W      # (free_time, last task) per slot
    heapq.heapify(workers)
    done = 0
    while ready_heap:
        rt, k = heapq.heappop(ready_heap)
        t = tasks[k]
        if t["slot"]:
            free, last = heapq.heappop(workers)
            start = max(rt, free)
            if free > rt and last is not None:
                det[k] = last            # waited for the executor, not deps
            heapq.heappush(workers, (start + t["dur"], k))
        else:
            start = rt
        end[k] = start + t["dur"]
        done += 1
        for dep_k in dependents[k]:
            n_deps[dep_k] -= 1
            if n_deps[dep_k] == 0:
                r, d = ready_time(dep_k)
                det.setdefault(dep_k, d)
                heapq.heappush(ready_heap, (r, dep_k))
    assert done == len(tasks), "replay deadlock: cyclic deps in the DAG"

    # steady-state step time: width of the LAST iteration window
    def iter_end(it):
        return max(v for k, v in end.items() if k[0] == it)
    total = iter_end(n_iterations - 1)
    step = (total - iter_end(n_iterations - 2)) if n_iterations > 1 else total

    busy = [0.0] * dag.n_stages
    last_it = n_iterations - 1
    for k, t in tasks.items():
        if k[0] == last_it and t["slot"] and t["device"] >= 0:
            busy[stage_of(t["device"])] += t["dur"] / max(dag.n_rows, 1)
    idle = [max(step - b, 0.0) for b in busy]

    # critical path: walk determining predecessors back from the last task
    tail = max((k for k in end), key=lambda k: end[k])
    path = []
    k = tail
    seen = set()
    while k is not None and k not in seen:
        seen.add(k)
        path.append((tasks[k]["label"], tasks[k]["dur"]))
        k = det.get(k)
    path.reverse()
    return ReplayResult(step_time_s=step, total_time_s=total,
                        n_iterations=n_iterations,
                        per_stage_busy_s=busy, per_stage_idle_s=idle,
                        critical_path=path)


# ---------------------------------------------------------------------------
# Calibration: measured micro-runs on the live mesh
# ---------------------------------------------------------------------------

def calibrate(mesh, *, V: int = 128, h: int = 32, n_classes: int = 4,
              fista_iters: int = 15, iters: int = 20, reps: int = 3,
              chain: int = 4,
              costs: Optional[CostTable] = None) -> CostTable:
    """Fill a :class:`CostTable` from micro-runs on `mesh` (the same
    warmup + ``block_until_ready`` discipline as the comm benches).

    Tolls are DIFFERENTIAL: an empty shard_map step prices
    ``step:dispatch``; steps with a length-`chain` sequence of collectives
    (each separated by a small eltwise op, the way the real step interleaves
    decode/compute) price ``collective:<prim>`` as the per-collective
    increment over the empty step — on the CPU device simulator that toll is
    thread-wake/context-switch, the very thing the overlap schedule removes
    from the critical path. Compute rates are calibrated IN THE DAG'S OWN
    UNITS: the micro fn's jaxpr is walked with the same `_accumulate` used
    for extraction, and the rate is (jaxpr flops-or-bytes) / measured
    seconds — so systematic over-counting of fused elementwise traffic
    cancels between calibration and prediction.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.analysis.costs import timed

    costs = costs or CostTable()
    axes = tuple(mesh.axis_names)
    world = int(np.prod(list(mesh.shape.values())))
    ring_axis = "model" if "model" in mesh.shape else axes[-1]
    n_ring = mesh.shape[ring_axis]
    perm = [(i, (i + 1) % n_ring) for i in range(n_ring)]
    rows = max(V // max(world // n_ring, 1), 1)
    spec = P(axes)

    def smap(f):
        return jax.jit(shard_map(f, mesh=mesh, in_specs=(spec,),
                                 out_specs=spec, check_vma=False))

    x = jax.device_put(
        jnp.ones((world * 4, h), jnp.float32),
        NamedSharding(mesh, spec))

    t_empty = timed(smap(lambda v: v + 1.0), x, iters=iters, reps=reps)
    costs.set("step:dispatch", t_empty)

    # tolls are measured with a compute burn BETWEEN consecutive collectives
    # — back-to-back collectives on an idle mesh rendezvous in lockstep and
    # look nearly free, while the real step's collectives sit between heavy
    # solver phases where every device arrives with scheduling skew the
    # rendezvous must absorb (on the CPU simulator that skew, not the wire,
    # IS the toll — psum barriers cost ~100x their lockstep price there)
    def burn(v):
        for _ in range(30):
            v = jnp.maximum(v * 1.0001 + 0.01, 0.0) - 0.005
        return v

    def burn_chain(v):
        for _ in range(chain):
            v = burn(v)
        return v

    def pp_chain(v):
        for _ in range(chain):
            v = jax.lax.ppermute(burn(v), ring_axis, perm)
        return v

    def ps_chain(v):
        for _ in range(chain):
            v = burn(v)
            v = v + jax.lax.psum(jnp.sum(v), axes) * 1e-9
        return v

    t_burn = timed(smap(burn_chain), x, iters=iters, reps=reps)
    t_pp = timed(smap(pp_chain), x, iters=iters, reps=reps)
    t_ps = timed(smap(ps_chain), x, iters=iters, reps=reps)
    toll_pp = max((t_pp - t_burn) / chain, 1e-9)
    toll_ps = max((t_ps - t_burn) / chain, 1e-9)
    costs.set("collective:ppermute", toll_pp)
    costs.set("collective:psum", toll_ps)
    costs.set("collective:all_gather", toll_ps)

    # async issue: the collective's result is NOT consumed in-body (it only
    # leaves the step), so the rendezvous rides behind the returned compute
    def pp_issue(v):
        return v + 1.0, jax.lax.ppermute(v, ring_axis, perm)

    t_iss = timed(smap(pp_issue), x, iters=iters, reps=reps)
    # an async start can never cost more than the full blocking rendezvous —
    # clamping keeps the replay's overlap-vs-blocking ordering noise-proof
    toll_iss = min(max(t_iss - t_empty, 1e-10), toll_pp)
    costs.set("collective:ppermute:issue", toll_iss)
    costs.set("collective:psum:issue", min(toll_iss, toll_ps))
    costs.set("collective:all_gather:issue", min(toll_iss, toll_ps))

    # compute rates, in the DAG's own counting convention (single device —
    # replay models multi-device core contention via executor slots)
    a = jnp.ones((rows, h), jnp.float32)
    w = jnp.ones((h, h), jnp.float32)

    def dots(p, W):
        for _ in range(8):
            p = p @ W
        return p

    jd = jax.jit(dots)
    seg = Segment(-1)
    _accumulate(seg, jax.make_jaxpr(dots)(a, w).jaxpr)
    t_dot = timed(jd, a, w, iters=iters, reps=reps)
    costs.set("rate:dot_flops", max(seg.flops / t_dot, 1.0))
    costs.set("rate:op_overhead", 5e-8)

    # elementwise throughput in jaxpr-out-bytes/s, measured on a SOLVER-
    # SHAPED probe: one layer-vmapped pass of the FULL per-iteration update
    # family (p/W/b/z incl. the FISTA z_last scan, q, dual) on a single
    # device with no collectives. The solver body is ~a thousand small eqns
    # that XLA fuses aggressively (a toy eltwise chain under-estimates the
    # effective rate by ~an order of magnitude, and leaving the fista scan
    # out under-estimates it ~3x), so the rate is calibrated on real solver
    # compute — the DAG's systematic fusion over-count then cancels between
    # calibration and prediction. The probe runs under the ambient
    # REPRO_KERNELS dispatch, so interpret-mode per-kernel overhead is
    # priced into the rate at the body's own op mix.
    from repro.core import subproblems as sp

    def layer_fam(p, W, b, z, q, u):
        r = sp._residual(p, W, b, z, True)
        pn, _, rn = sp.update_p(p, W, b, z, q, u, 1.0, 1.0, 1.0, r0=r,
                                use_kernels=True)
        Wn, _, rw = sp.update_W(pn, W, b, z, q, u, 1.0, 1.0, 1.0,
                                first=False, r0=rn, use_kernels=True)
        a = z - rw
        zn = sp._zupdate(a, q, z, 1.0, True)
        qn = sp.update_q(pn, u, jnp.maximum(zn, 0.0), 1.0, 1.0, None)
        return pn, Wn, a, zn, qn, u + (pn - qn)

    def solver_probe(p, W, b, z, q, u, labels, mask):
        pn, Wn, a2, zn, qn, un = jax.vmap(layer_fam)(p, W, b, z, q, u)
        # the step solves z_L on the last local layer only
        zl = sp.update_z_last(a2[-1], z[-1], labels, mask, 1.0, fista_iters,
                              n_classes=n_classes, use_kernels=True)
        return pn, Wn, zn, zl, qn, un

    m_loc = 2
    pa = jnp.ones((m_loc, rows, h), jnp.float32) * 0.1
    wa = jnp.stack([w] * m_loc) / h
    ba = jnp.zeros((m_loc, h), jnp.float32)
    probe_args = (pa, wa, ba, pa, pa, pa,
                  jnp.zeros((rows,), jnp.int32), jnp.ones((rows,)))
    seg = Segment(-1)
    _accumulate(seg, jax.make_jaxpr(solver_probe)(*probe_args).jaxpr)
    t_probe = timed(jax.jit(solver_probe), *probe_args, iters=iters,
                    reps=reps)
    t_res = max(t_probe - seg.flops / costs.get("rate:dot_flops")
                - seg.n_eqns * costs.get("rate:op_overhead"),
                0.05 * t_probe)
    costs.set("rate:eltwise_bytes", max(seg.bytes / t_res, 1.0))

    # link: the CPU simulator "wire" is a memcpy — price bandwidth at the
    # measured eltwise stream rate and fold per-message latency into tolls
    costs.set("link:latency", toll_iss / 4.0)
    costs.set("link:bandwidth", costs.get("rate:eltwise_bytes"))
    costs.meta.update({"mesh": dict(mesh.shape), "V": V, "h": h,
                       "backend": jax.default_backend(),
                       "world": world})
    return costs


# ---------------------------------------------------------------------------
# Replay-searched schedule choices (hand rules kept as documented fallbacks)
# ---------------------------------------------------------------------------

def choose_psum_mode(codec, shape, world_size: int,
                     costs: Optional[CostTable] = None) -> str:
    """The psum collective the REPLAY model picks: price all three physical
    realizations with the link model and return the cheapest.

      * ``psum`` (plain fp32): ring reduce-scatter + all-gather, ``2*(w-1)``
        rounds each moving ``4n/w`` bytes,
      * ``code_psum``: same rounds over the int32 code container, plus the
        shared-grid encode pass,
      * ``gather``: ``w-1`` all-gather rounds over the PACKED container
        (``bits/8`` bytes per element) plus the ``w``-way local decode-sum.

    With no `costs`, falls back to the hand-derived ring byte rule
    :func:`repro.comm.transport.psum_mode` (``gather`` iff
    ``world*bits < 64``) — the documented PR-5 fallback. In the bandwidth-
    dominated limit (latency → 0, compute → 0) the replay prices reduce to
    exactly that rule; a latency-dominated link shifts the break-even
    toward ``gather`` (half the rounds).
    """
    from repro.comm.codecs import Fp32Codec
    from repro.comm.transport import psum_mode
    if costs is None:
        return psum_mode(codec, world_size)
    if isinstance(codec, Fp32Codec) or codec.bits >= 32:
        return "psum"
    link = costs.link
    w = int(world_size)
    n = int(math.prod(int(s) for s in shape))
    elt = costs.get("rate:eltwise_bytes")
    quant = 2 * 4 * n / elt                      # encode: read x, write codes
    t_psum = 2 * (w - 1) * link.transfer_time(4 * n / w)
    t_code = 2 * (w - 1) * link.transfer_time(4 * n / w) + quant
    body = math.ceil(n * codec.bits / 8)
    decode = w * 2 * n / elt                     # unpack+sum each arrival
    t_gather = (w - 1) * link.transfer_time(body) + quant + decode
    prices = {"psum": t_psum, "code_psum": t_code, "gather": t_gather}
    return min(prices, key=lambda m: (prices[m], m))


def choose_overlap(dag_baseline: StepDag, dag_overlap: StepDag,
                   costs: Optional[CostTable] = None, *,
                   n_workers: Optional[int] = None) -> bool:
    """Replay both step variants and return True iff the double-buffered
    schedule is predicted no slower. With no `costs` the hand default (the
    PR-4 result: overlap on) is returned."""
    if costs is None:
        return True
    base = replay(dag_baseline, costs, n_workers=n_workers)
    over = replay(dag_overlap, costs, n_workers=n_workers)
    return over.step_time_s <= base.step_time_s


class ScheduleCostModel:
    """Per-boundary bit-width schedule → predicted step seconds: the
    ``objective="walltime"`` hook of
    :class:`repro.comm.controller.BitWidthController`.

    `edge_bytes_fn(schedule)` maps a controller schedule (one bits entry
    per managed edge) to per-link physical wire bytes keyed by the DAG's
    ppermute edge names — for a :class:`~repro.comm.transport.PaddedWire`
    container step that is the (schedule-independent) container capacity;
    for a codec-formatted wire it is the packed payload at the scheduled
    width. Predictions are memoized: the controller probes many candidate
    schedules per control step and hysteresis keeps the distinct set small.
    """

    def __init__(self, dag: StepDag, costs: CostTable,
                 edge_bytes_fn: Callable[[Tuple[int, ...]], Dict[str, int]],
                 *, n_workers: Optional[int] = None):
        self.dag = dag
        self.costs = costs
        self.edge_bytes_fn = edge_bytes_fn
        self.n_workers = n_workers
        self._cache: Dict[Tuple[int, ...], float] = {}

    def __call__(self, schedule: Sequence[int]) -> float:
        key = tuple(int(b) for b in schedule)
        if key not in self._cache:
            dag = self.dag.with_wire_bytes(self.edge_bytes_fn(key))
            self._cache[key] = replay(dag, self.costs,
                                      n_workers=self.n_workers).step_time_s
        return self._cache[key]
