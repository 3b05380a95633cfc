"""Program-contract linter: static verification of the traced/lowered step.

The repo's correctness story lives at trace level: the pdADMM-G step is only
paper-faithful *and* fast if the compiled program has exactly the promised
shape — fused kernel dispatch counts, carried ppermutes under overlap,
packed wire dtypes and physical byte counts, integrity headers beside
payloads, donation markers. This module checks all of that **without
executing a single iteration**: every artifact comes from abstract tracing
(`jax.make_jaxpr` on `jax.ShapeDtypeStruct`s), lowering (`.lower().as_text()`)
or — optionally — compilation, never from running the step.

Schema
------
A **contract** is a named invariant over one traced step configuration::

    @contract("schedule.carried", severity="error",
              description="in-flight slabs leaving through the carry")
    def _carried(view):
        got = sum(1 for p in view.profile if p["carried"])
        if got != view.plan.n_carried:
            yield (f"{got} carried ppermutes, plan says "
                   f"{view.plan.n_carried}", {"got": got})

  * the key is ``family.name``; the family (``dispatch`` / ``schedule`` /
    ``wire`` / ``memory`` / ``dtype`` / ``cache``) is the key's first
    segment and is what CLI/report grouping keys on,
  * the check receives a :class:`ProgramView` — lazily traced artifacts of
    one configuration — and yields ``(message, details)`` per violation;
    each becomes a :class:`Finding` with the contract's key and severity,
  * severities: ``error`` (CI-failing — the program broke a promise),
    ``warn`` (suspicious but running it won't be wrong), ``info``.

The *declarative* half of every step contract is
:func:`repro.parallel.stage_parallel.step_program_plan` (and
:func:`repro.comm.transport.psum_program_plan` for the compressed psum):
the expected dispatch/schedule/wire plan is computed next to the code that
owns the invariant, and the checks here only compare trace against plan.
A new step variant (2D mesh, MPMD transport, ...) therefore ships by
extending the plan builder + registering a :class:`StepSpec` — not by
writing new walkers.

Registering a configuration::

    STEP_SPECS += (StepSpec(name="mpmd_2d", mesh=(2, 4), overlap=True), )

Mutation testing (and the `tests/test_contracts.py` battery) drives the
same engine with a *declared* spec but a *mutated* trace:
``check_contracts(spec, overrides={"donate": False})`` traces the step
without donation while the plan still promises markers — the
``memory.donation`` contract must fire. ``wrap=`` post-composes a function
onto the step before tracing (e.g. an f64 cast to exercise
``dtype.no_f64``), ``variants=`` overrides the cache-probe flip table and
``pinned=`` the expected kwarg set.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.analysis.jaxpr_tools import (collective_profile, count_primitive,
                                        jaxprs_with, _sub_jaxprs)

# ---------------------------------------------------------------------------
# Findings and the contract registry
# ---------------------------------------------------------------------------

SEVERITIES = ("error", "warn", "info")


@dataclasses.dataclass(frozen=True)
class Finding:
    """One contract violation (or informational note) on one config."""
    key: str                     # "family.name"
    severity: str                # error | warn | info
    config: str                  # registered spec name (or file path)
    message: str
    details: dict = dataclasses.field(default_factory=dict)

    @property
    def family(self) -> str:
        return self.key.split(".", 1)[0]

    def to_dict(self) -> dict:
        return {"key": self.key, "severity": self.severity,
                "config": self.config, "message": self.message,
                "details": self.details}


@dataclasses.dataclass(frozen=True)
class Contract:
    key: str
    severity: str
    description: str
    check: Callable                      # (view) -> iterable[(msg, details)]

    @property
    def family(self) -> str:
        return self.key.split(".", 1)[0]


CONTRACTS: Dict[str, Contract] = {}


def contract(key: str, *, severity: str, description: str):
    """Register a check function under `key` (``family.name``)."""
    assert severity in SEVERITIES, severity

    def deco(fn):
        assert key not in CONTRACTS, f"duplicate contract {key}"
        CONTRACTS[key] = Contract(key, severity, description, fn)
        return fn

    return deco


# ---------------------------------------------------------------------------
# Registered step configurations (declarative — no jax objects held)
# ---------------------------------------------------------------------------

GRID_RANGE = (-2.0, 6.0)     # calibration range every registered grid uses

# the kwarg-only surface make_distributed_step pins (the step cache key)
PINNED_STEP_KWARGS = frozenset(
    {"overlap", "donate", "p_codec", "q_codec", "wire", "health", "faults"})


@dataclasses.dataclass(frozen=True)
class StepSpec:
    """One registered `make_distributed_step` configuration, held as plain
    data so specs import (and list) without touching jax."""
    name: str
    mesh: Tuple[int, int] = (2, 2)       # (data, model)
    V: int = 64
    h: int = 32
    L: int = 4
    n_classes: int = 4
    fista_iters: int = 5
    solver_grid_bits: int = 0    # >0: pdADMM-G-Q solver (backtracking p)
    overlap: bool = False
    donate: bool = False
    p_bits: int = 0              # wire codec bits (0 -> config default)
    q_bits: int = 0
    container: Tuple[int, ...] = ()      # PaddedWire widths
    health: bool = False
    fault_flip_rate: float = 0.0
    cache_probe: bool = False    # run the cache family from this spec
    check_ragged: bool = False   # re-trace at a ragged V (pad-to-tile)
    check_compile: bool = False  # compile for aliasing/copy checks

    def config(self):
        from repro.core.pdadmm import ADMMConfig
        from repro.core.quantize import uniform_grid
        grid = None
        if self.solver_grid_bits:
            grid = uniform_grid(self.solver_grid_bits, *GRID_RANGE)
        return ADMMConfig(nu=1e-2, rho=1.0, fista_iters=self.fista_iters,
                          quantize_p=grid is not None,
                          quantize_q=grid is not None, grid=grid)

    def kwargs(self) -> dict:
        """The actual `make_distributed_step` kwargs this spec declares."""
        from repro.comm import codecs as C, faults as FT
        from repro.comm.transport import PaddedWire
        from repro.core.quantize import uniform_grid

        def grid_codec(bits):
            return C.GridCodec(uniform_grid(bits, *GRID_RANGE)) \
                if bits else None

        wire = None
        if self.container:
            wire = PaddedWire.from_grids(
                {b: uniform_grid(b, *GRID_RANGE) for b in self.container})
        faults = None
        if self.fault_flip_rate:
            faults = FT.FaultPlan(seed=0, flip_rate=self.fault_flip_rate)
        return dict(overlap=self.overlap, donate=self.donate,
                    p_codec=grid_codec(self.p_bits),
                    q_codec=grid_codec(self.q_bits),
                    wire=wire, health=self.health, faults=faults)


STEP_SPECS: Tuple[StepSpec, ...] = (
    StepSpec(name="baseline", cache_probe=True, check_ragged=True),
    StepSpec(name="overlap", overlap=True),
    StepSpec(name="donate", donate=True, check_compile=True),
    StepSpec(name="int8_wire", p_bits=8, q_bits=8),
    StepSpec(name="int4_wire", p_bits=4, q_bits=4),
    StepSpec(name="mixed_wire", p_bits=8, q_bits=16),
    StepSpec(name="quantized_solver", solver_grid_bits=8, check_ragged=True),
    StepSpec(name="container", container=(4, 8, 16)),
    StepSpec(name="container_overlap", container=(4, 8, 16), overlap=True),
    StepSpec(name="health", health=True),
    StepSpec(name="faults", health=True, fault_flip_rate=0.05),
)


@dataclasses.dataclass(frozen=True)
class PsumSpec:
    """One registered `quantized_psum` point: codec bits x world size.
    world=4 keeps every spec traceable on the 8-device CI harness."""
    name: str
    bits: int
    world: int = 4
    rows: int = 8
    cols: int = 16

    def codec(self):
        from repro.comm import codecs as C
        return C.FP32 if self.bits >= 32 else C.AffineCodec(self.bits)


PSUM_SPECS: Tuple[PsumSpec, ...] = (
    PsumSpec(name="psum_int4_w4", bits=4),      # 16 < 64  -> gather
    PsumSpec(name="psum_int8_w4", bits=8),      # 32 < 64  -> gather
    PsumSpec(name="psum_int16_w4", bits=16),    # 64 >= 64 -> code_psum
    PsumSpec(name="psum_fp32_w4", bits=32),     # uncompressed psum
)


def get_spec(name: str):
    for s in STEP_SPECS + PSUM_SPECS:
        if s.name == name:
            return s
    raise KeyError(f"no registered spec {name!r}; known: "
                   f"{[s.name for s in STEP_SPECS + PSUM_SPECS]}")


# ---------------------------------------------------------------------------
# Traced-program views (lazy; nothing executes)
# ---------------------------------------------------------------------------

def _mesh_for(shape: Tuple[int, int]):
    from repro.launch.mesh import make_mesh
    need = math.prod(shape)
    devs = jax.devices()
    if len(devs) < need:
        raise RuntimeError(
            f"{need}-device mesh {shape} needs XLA_FLAGS="
            f"--xla_force_host_platform_device_count>={need} "
            f"(have {len(devs)}); the lint CLI sets this up for you")
    return make_mesh(shape, ("data", "model"), devices=devs[:need])


def _walk_jaxprs(jaxpr):
    yield jaxpr
    for eqn in jaxpr.eqns:
        for sub in _sub_jaxprs(eqn):
            yield from _walk_jaxprs(sub)


def _pallas_eqns(jaxpr):
    """(kernel-body base name, eqn) of every pallas_call, vmap's
    ``_batched`` suffix normalized away."""
    for jx in _walk_jaxprs(jaxpr):
        for eqn in jx.eqns:
            if eqn.primitive.name != "pallas_call":
                continue
            info = eqn.params.get("name_and_src_info")
            name = getattr(info, "name", None) or eqn.params.get("name")
            if not name:
                # this JAX keeps the body's name only in its debug info:
                # "<name> at <file>:<line>"
                src = getattr(eqn.params["jaxpr"].debug_info,
                              "func_src_info", None) or "?"
                name = src.split(" at ", 1)[0]
            if name.endswith("_batched"):
                name = name[:-len("_batched")]
            yield name, eqn


def _pallas_counts(jaxpr) -> Dict[str, int]:
    """pallas_call eqns per kernel-body base name."""
    out: Dict[str, int] = {}
    for name, _ in _pallas_eqns(jaxpr):
        out[name] = out.get(name, 0) + 1
    return out


def _ppermute_moves(jaxpr):
    """Every ppermute's moved payload, in issue order: (dtype, bytes)."""
    moves = []
    for body in jaxprs_with(jaxpr, "ppermute"):
        for eqn in body.eqns:
            if eqn.primitive.name != "ppermute":
                continue
            a = eqn.outvars[0].aval
            moves.append((str(a.dtype),
                          math.prod(a.shape) * a.dtype.itemsize))
    return moves


def _f64_offenders(jaxpr):
    """Primitives touching a float64 aval anywhere in the program."""
    hits = []
    for jx in _walk_jaxprs(jaxpr):
        for eqn in jx.eqns:
            for v in list(eqn.invars) + list(eqn.outvars):
                aval = getattr(v, "aval", None)
                if aval is not None and \
                        str(getattr(aval, "dtype", "")) == "float64":
                    hits.append(eqn.primitive.name)
                    break
    return hits


class ProgramView:
    """Lazily traced artifacts of one step configuration.

    `plan` always reflects the spec's DECLARED kwargs; `overrides` mutates
    only what is traced (the mutation-testing hook), `wrap` post-composes a
    function onto the step before tracing.
    """

    def __init__(self, spec: StepSpec, *, overrides: Optional[dict] = None,
                 wrap: Optional[Callable] = None):
        self.spec = spec
        self.overrides = dict(overrides or {})
        self.wrap = wrap
        self._cache: dict = {}

    def _memo(self, key, fn):
        if key not in self._cache:
            self._cache[key] = fn()
        return self._cache[key]

    @property
    def mesh(self):
        return self._memo("mesh", lambda: _mesh_for(self.spec.mesh))

    @property
    def plan(self):
        from repro.parallel import stage_parallel as SP

        def build():
            return SP.step_program_plan(
                self.mesh, self.spec.L, self.spec.n_classes,
                self.spec.config(), V=self.spec.V, h=self.spec.h,
                **self.spec.kwargs())
        return self._memo("plan", build)

    def _build(self, kwargs, V):
        """(step, carry struct, arg structs) for `kwargs` at node count V —
        everything abstract, mirroring `trace_step_dag`'s construction."""
        from repro.comm import codecs as C, faults as FT
        from repro.parallel import stage_parallel as SP
        spec = self.spec
        step, _ = SP.make_distributed_step(
            self.mesh, spec.L, spec.n_classes, spec.config(), **kwargs)
        f32, i32 = jnp.float32, jnp.int32
        sds = jax.ShapeDtypeStruct
        L, h = spec.L, spec.h
        st = SP.StackState(p=sds((L, V, h), f32), W=sds((L, h, h), f32),
                           b=sds((L, h), f32), z=sds((L, V, h), f32),
                           q=sds((L, V, h), f32), u=sds((L, V, h), f32))
        args = [sds((V, h), f32), sds((V,), i32), sds((V,), f32)]
        n_stages = self.mesh.shape["model"]
        if kwargs.get("wire") is not None:
            args.append(sds((2, n_stages), i32))
        sentinel = kwargs.get("health") or kwargs.get("faults") is not None
        if kwargs.get("overlap"):
            qc = kwargs.get("q_codec") or C.FP32
            primer = SP.make_overlap_primer(self.mesh, qc,
                                            wire=kwargs.get("wire"),
                                            sentinel=bool(sentinel))
            pargs = (st.q, st.u)
            if kwargs.get("wire") is not None:
                pargs += (args[-1],)
            carry = (st, jax.eval_shape(primer, *pargs))
        else:
            carry = st
        if sentinel:
            primer = SP.make_sentinel_primer(
                self.mesh, kwargs.get("p_codec") or C.FP32,
                kwargs.get("q_codec") or C.FP32, wire=kwargs.get("wire"))
            pargs = (st.q, st.u, st.p)
            if kwargs.get("wire") is not None:
                pargs += (args[-1],)
            good = jax.eval_shape(primer, *pargs)
            if kwargs.get("overlap"):
                st_c, fly = carry
                carry = ((st_c, good), fly)
            else:
                carry = (carry, good)
            args.append(jax.eval_shape(lambda: FT.null_controls(n_stages)))
        fn = step
        if self.wrap is not None:
            fn = self.wrap(step)
        return fn, carry, tuple(args)

    @property
    def trace_kwargs(self) -> dict:
        kw = self.spec.kwargs()
        kw.update(self.overrides)
        return kw

    @property
    def _traced(self):
        def build():
            fn, carry, args = self._build(self.trace_kwargs, self.spec.V)
            return fn, carry, args, jax.make_jaxpr(fn)(carry, *args)
        return self._memo("traced", build)

    @property
    def jaxpr(self):
        return self._traced[3].jaxpr

    @property
    def carry_struct(self):
        return self._traced[1]

    @property
    def profile(self):
        return self._memo("profile",
                          lambda: collective_profile(self.jaxpr))

    @property
    def pallas_counts(self):
        return self._memo("pallas", lambda: _pallas_counts(self.jaxpr))

    @property
    def fista_operands(self):
        """Shapes of the z_prev, z_cur and a operands of every fused FISTA
        step, in trace order."""
        from repro.kernels import ops
        kernel = ops.KERNEL_NAMES["fista_zlast"]
        return self._memo("fista", lambda: [
            tuple(tuple(v.aval.shape) for v in eqn.invars[:3])
            for name, eqn in _pallas_eqns(self.jaxpr) if name == kernel])

    @property
    def ppermute_moves(self):
        return self._memo("moves", lambda: _ppermute_moves(self.jaxpr))

    @property
    def lowered_text(self) -> str:
        def build():
            fn, carry, args = self._traced[:3]
            return fn.lower(carry, *args).as_text()
        return self._memo("lowered", build)

    @property
    def compiled_text(self) -> str:
        def build():
            fn, carry, args = self._traced[:3]
            return fn.lower(carry, *args).compile().as_text()
        return self._memo("compiled", build)

    def ragged_view(self) -> "ProgramView":
        """The same configuration traced at a V whose per-row shard is
        ragged against every kernel tile (pad-to-tile must kick in)."""
        def build():
            n_rows = self.spec.mesh[0]
            v = ProgramView(dataclasses.replace(self.spec,
                                                V=n_rows * 17,
                                                name=self.spec.name),
                            overrides=self.overrides, wrap=self.wrap)
            v._cache["mesh"] = self.mesh
            return v
        return self._memo("ragged", build)

    def fingerprint(self) -> tuple:
        """Cheap trace-level identity used by the cache contracts: two
        kwarg points MUST differ somewhere in here to be cache-distinct."""
        prof = self.profile
        return (len(prof),
                sum(1 for p in prof if p["carried"]),
                tuple(p["dtype"] for p in prof),
                tuple(self.ppermute_moves),
                count_primitive(self.jaxpr, "xor") > 0,
                self.lowered_text.count("jax.buffer_donor"),
                len(self._traced[2]))


class PsumView:
    """Lazily traced `quantized_psum` program on a 1D world-sized mesh."""

    def __init__(self, spec: PsumSpec, *, codec_override=None,
                 mode: Optional[str] = None):
        self.spec = spec
        self.codec_override = codec_override
        self.mode = mode
        self._cache: dict = {}

    @property
    def plan(self):
        from repro.comm import transport as T
        if "plan" not in self._cache:
            self._cache["plan"] = T.psum_program_plan(
                self.spec.codec(), (self.spec.rows, self.spec.cols),
                self.spec.world, self.mode)
        return self._cache["plan"]

    @property
    def jaxpr(self):
        from repro.comm.transport import quantized_psum
        from repro.launch.mesh import make_mesh
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        if "jaxpr" not in self._cache:
            spec = self.spec
            devs = jax.devices()
            if len(devs) < spec.world:
                raise RuntimeError(
                    f"psum spec {spec.name} needs {spec.world} devices "
                    f"(have {len(devs)})")
            m = make_mesh((spec.world,), ("d",), devices=devs[:spec.world])
            codec = self.codec_override or spec.codec()
            f = shard_map(lambda x: quantized_psum(x, "d", codec,
                                                   mode=self.mode),
                          mesh=m, in_specs=P("d"), out_specs=P("d"),
                          check_vma=False)
            x = jax.ShapeDtypeStruct((spec.world * spec.rows, spec.cols),
                                     jnp.float32)
            self._cache["jaxpr"] = jax.make_jaxpr(f)(x).jaxpr
        return self._cache["jaxpr"]

    def payload_ops(self):
        """(primitive, dtype, operand bytes) of every payload-bearing
        collective (psum of non-scalars / all_gather) in the trace."""
        ops = []
        for jx in _walk_jaxprs(self.jaxpr):
            for eqn in jx.eqns:
                if eqn.primitive.name not in ("psum", "all_gather"):
                    continue
                a = eqn.invars[0].aval
                if not a.shape:          # world-size psum(1) bookkeeping
                    continue
                ops.append((eqn.primitive.name, str(a.dtype),
                            math.prod(a.shape) * a.dtype.itemsize))
        return ops


# ---------------------------------------------------------------------------
# dispatch family
# ---------------------------------------------------------------------------

@contract("dispatch.pallas_calls", severity="error",
          description="exact pallas_call count per kernel matches the "
                      "step's dispatch plan under the current policy")
def _dispatch_counts(view):
    got, want = view.pallas_counts, view.plan.pallas_calls
    if got != want:
        yield (f"per-kernel pallas_call counts {got} != plan {want} "
               f"(policy resolves kernels "
               f"{'on' if want else 'off'})",
               {"got": got, "want": want})


@contract("dispatch.ragged_fallback", severity="error",
          description="ragged node counts keep the kernel path "
                      "(pad-to-tile; no silent ref fallback)")
def _dispatch_ragged(view):
    if not view.spec.check_ragged or not view.plan.pallas_calls:
        return
    ragged = view.ragged_view()
    got = ragged.pallas_counts
    if got != view.plan.pallas_calls:
        yield (f"ragged V={ragged.spec.V} dispatches {got} != "
               f"tile-aligned plan {view.plan.pallas_calls} — "
               f"silent ref fallback",
               {"ragged_V": ragged.spec.V, "got": got})


@contract("dispatch.fista_operands", severity="error",
          description="every fused FISTA step reads one layer's logit "
                      "columns, [pad(V_loc), pad(C)] (not the layer stack, "
                      "not the full width)")
def _dispatch_fista_operands(view):
    want = view.plan.fista_operand
    if want is None:
        return
    got = sorted({s for call in view.fista_operands for s in call})
    if got != [want]:
        yield (f"fused FISTA steps read operands {got}, plan says one "
               f"layer's logit columns {want}",
               {"got": got, "want": want})


# ---------------------------------------------------------------------------
# schedule family
# ---------------------------------------------------------------------------

@contract("schedule.ppermute_count", severity="error",
          description="total boundary ppermutes (payload + headers) match "
                      "the plan")
def _sched_count(view):
    got, want = len(view.profile), len(view.plan.edge_events)
    if got != want:
        yield (f"{got} ppermutes traced, plan schedules {want}",
               {"got": got, "want": want})


@contract("schedule.carried", severity="error",
          description="in-flight slabs leaving through the carry (2 under "
                      "overlap, else 0)")
def _sched_carried(view):
    got = sum(1 for p in view.profile if p["carried"])
    if got != view.plan.n_carried:
        yield (f"{got} carried ppermutes, plan says {view.plan.n_carried}",
               {"got": got, "want": view.plan.n_carried})


@contract("schedule.work_to_consumer", severity="error",
          description="overlap hides consumed exchanges behind solver "
                      "work; the baseline ordering is exactly fused")
def _sched_work(view):
    floor = view.plan.min_work_to_consumer
    consumed = [p for p in view.profile if not p["carried"]]
    if floor == 0:
        bad = [p["work_to_consumer"] for p in consumed
               if p["work_to_consumer"] != 0]
        if bad:
            yield (f"fused schedule has work between issue and consume: "
                   f"{bad}", {"work": bad})
        return
    payload = [p for p in consumed if p["dtype"] != "int32"]
    lazy = [p["work_to_consumer"] for p in payload]
    if any(w < floor for w in lazy):
        yield (f"consumed exchange sits on the critical path: "
               f"work_to_consumer {lazy} < {floor}",
               {"work": lazy, "floor": floor})


@contract("schedule.fault_injector", severity="error",
          description="xor injection machinery present iff an active "
                      "FaultPlan is declared")
def _sched_xor(view):
    has_xor = count_primitive(view.jaxpr, "xor") > 0
    if has_xor != view.plan.expects_xor:
        yield (f"xor machinery {'present' if has_xor else 'absent'}, plan "
               f"expects {'it' if view.plan.expects_xor else 'none'}",
               {"has_xor": has_xor})


@contract("schedule.psum_mode", severity="error",
          description="the compressed psum's physical collective matches "
                      "the world*bits < 64 rule")
def _sched_psum(view):
    if not isinstance(view, PsumView):
        return
    plan = view.plan
    ops = view.payload_ops()
    prims = {(p, d) for p, d, _ in ops}
    if (plan.collective, plan.operand_dtype) not in prims:
        yield (f"mode {plan.mode} promises {plan.collective}"
               f"[{plan.operand_dtype}], trace has {sorted(prims)}",
               {"want": [plan.collective, plan.operand_dtype],
                "got": sorted(prims)})
    has_handshake = count_primitive(view.jaxpr, "pmin") > 0
    if plan.mode != "psum" and has_handshake != plan.handshake:
        yield (f"affine min/max handshake "
               f"{'present' if has_handshake else 'absent'}, plan expects "
               f"{plan.handshake}", {"handshake": has_handshake})


# ---------------------------------------------------------------------------
# wire family
# ---------------------------------------------------------------------------

@contract("wire.dtypes", severity="error",
          description="each boundary ppermute moves the codec's physical "
                      "container dtype, in issue order")
def _wire_dtypes(view):
    got = [p["dtype"] for p in view.profile]
    want = [d for _, d, _ in view.plan.edge_events]
    if got != want:
        yield (f"wire dtypes {got} != plan {want} (issue order "
               f"{[e for e, _, _ in view.plan.edge_events]})",
               {"got": got, "want": want})


@contract("wire.ppermute_bytes", severity="error",
          description="physical bytes of each boundary ppermute equal the "
                      "codec/container accounting (payload_bytes/capacity)")
def _wire_bytes(view):
    got = view.ppermute_moves
    want = view.plan.edge_events
    if len(got) != len(want):
        return  # schedule.ppermute_count already fires
    for (edge, wdt, wb), (gdt, gb) in zip(want, got):
        if gb != wb:
            yield (f"{edge} moves {gb} B/link ({gdt}), accounting says "
                   f"{wb} B ({wdt}) — wire undercount",
                   {"edge": edge, "got": gb, "want": wb})


@contract("wire.psum_bytes", severity="error",
          description="the compressed psum's payload operand bytes equal "
                      "psum_wire_bytes' physical accounting")
def _wire_psum_bytes(view):
    if not isinstance(view, PsumView):
        return
    plan = view.plan
    match = [b for p, d, b in view.payload_ops()
             if (p, d) == (plan.collective, plan.operand_dtype)]
    if not match:
        return  # schedule.psum_mode already fires
    if plan.operand_bytes not in match:
        yield (f"{plan.collective}[{plan.operand_dtype}] payload bytes "
               f"{match} != psum_wire_bytes {plan.operand_bytes}",
               {"got": match, "want": plan.operand_bytes})


# ---------------------------------------------------------------------------
# memory family
# ---------------------------------------------------------------------------

@contract("memory.donation", severity="error",
          description="donate=True marks every carry leaf as a buffer "
                      "donor in the lowered program; donate=False none")
def _mem_donation(view):
    want = len(jax.tree_util.tree_leaves(view.carry_struct)) \
        if view.plan.donate else 0
    got = view.lowered_text.count("jax.buffer_donor")
    if got != want:
        yield (f"{got} jax.buffer_donor markers in the lowered program, "
               f"donation promises {want}", {"got": got, "want": want})


# ~2x headroom over the copies XLA:CPU emits for the donated 2x2 smoke
# step today (13 under ref, 77 under interpret — pallas interpret-mode
# lowering materializes block copies) — a jump past this means donation
# stopped eliding state copies
_COPY_BUDGETS = {"ref": 26, "interpret": 160}


@contract("memory.aliasing", severity="error",
          description="donated inputs are aliased to outputs in the "
                      "compiled module (donation actually took)")
def _mem_alias(view):
    if not view.spec.check_compile:
        return
    aliased = "input_output_alias" in view.compiled_text
    if aliased != view.plan.donate:
        yield (f"compiled input_output_alias "
               f"{'present' if aliased else 'absent'}, donation is "
               f"{view.plan.donate}", {"aliased": aliased})


@contract("memory.copies", severity="warn",
          description="compiled HLO copy count stays inside the budget "
                      "(donation keeps state updates in place)")
def _mem_copies(view):
    if not view.spec.check_compile:
        return
    from repro.kernels import ops
    budget = _COPY_BUDGETS["interpret" if ops.kernels_enabled() else "ref"]
    got = view.compiled_text.count(" copy(")
    if got > budget:
        yield (f"{got} copy ops in compiled HLO > budget {budget}",
               {"got": got, "budget": budget})


# ---------------------------------------------------------------------------
# dtype family
# ---------------------------------------------------------------------------

@contract("dtype.no_f64", severity="error",
          description="no float64 avals anywhere in the step (silent "
                      "f32->f64 promotion doubles wire and memory)")
def _dtype_f64(view):
    hits = _f64_offenders(view.jaxpr)
    if hits:
        yield (f"float64 avals flow through {sorted(set(hits))}",
               {"primitives": sorted(set(hits))})


@contract("dtype.weak_outputs", severity="warn",
          description="step outputs are strongly typed (weak-type leaks "
                      "respecialize downstream consumers)")
def _dtype_weak(view):
    weak = [str(a.dtype) for a in view._traced[3].out_avals
            if getattr(a, "weak_type", False)]
    if weak:
        yield (f"weakly-typed step outputs: {weak}", {"dtypes": weak})


# ---------------------------------------------------------------------------
# cache family
# ---------------------------------------------------------------------------

def _default_variants(spec: StepSpec) -> Dict[str, dict]:
    """Per pinned kwarg: the override that must change the traced program
    relative to `spec`'s base point."""
    from repro.comm import codecs as C, faults as FT
    from repro.comm.transport import PaddedWire
    from repro.core.quantize import uniform_grid
    return {
        "overlap": {"overlap": not spec.overlap},
        "donate": {"donate": not spec.donate},
        "p_codec": {"p_codec": C.GridCodec(uniform_grid(8, *GRID_RANGE))},
        "q_codec": {"q_codec": C.GridCodec(uniform_grid(16, *GRID_RANGE))},
        "wire": {"wire": PaddedWire.from_grids(
            {b: uniform_grid(b, *GRID_RANGE) for b in (4, 8, 16)}),
            "p_codec": None, "q_codec": None},
        "health": {"health": not spec.health},
        "faults": {"faults": FT.FaultPlan(seed=0, flip_rate=0.1)},
    }


@contract("cache.kwarg_set", severity="error",
          description="make_distributed_step's kwarg-only surface IS the "
                      "pinned cache-key set (a new kwarg must register "
                      "contracts before it ships)")
def _cache_kwargs(view):
    import inspect
    from repro.parallel import stage_parallel as SP
    if not view.spec.cache_probe:
        return
    sig = inspect.signature(SP.make_distributed_step)
    kwonly = {n for n, p in sig.parameters.items()
              if p.kind == inspect.Parameter.KEYWORD_ONLY}
    pinned = view._pinned if getattr(view, "_pinned", None) is not None \
        else PINNED_STEP_KWARGS
    if kwonly != set(pinned):
        yield (f"kwarg-only surface {sorted(kwonly)} != pinned cache-key "
               f"set {sorted(pinned)}",
               {"got": sorted(kwonly), "pinned": sorted(pinned)})


@contract("cache.kwarg_observable", severity="error",
          description="every pinned kwarg provably changes the traced "
                      "program (else the step cache hands back a stale "
                      "compilation)")
def _cache_observable(view):
    if not view.spec.cache_probe:
        return
    base = view.fingerprint()
    variants = view._variants if getattr(view, "_variants", None) is not None \
        else _default_variants(view.spec)
    for kw, delta in variants.items():
        flipped = ProgramView(view.spec, overrides=delta)
        flipped._cache["mesh"] = view.mesh
        if flipped.fingerprint() == base:
            yield (f"flipping {kw!r} leaves the traced program "
                   f"indistinguishable (fingerprint unchanged) — the step "
                   f"cache would serve a stale program", {"kwarg": kw})


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------

# the contracts a PsumSpec runs (step specs run everything else)
PSUM_CONTRACTS = frozenset({"schedule.psum_mode", "wire.psum_bytes"})


def check_contracts(spec, *, overrides: Optional[dict] = None,
                    wrap: Optional[Callable] = None,
                    variants: Optional[dict] = None,
                    pinned: Optional[Iterable[str]] = None,
                    families: Optional[Iterable[str]] = None):
    """Run every registered contract against one spec (by name or object);
    returns the list of :class:`Finding`. `overrides`/`wrap`/`variants`/
    `pinned` are the mutation-testing hooks (module docstring)."""
    if isinstance(spec, str):
        spec = get_spec(spec)
    if isinstance(spec, PsumSpec):
        view = PsumView(spec, codec_override=(overrides or {}).get("codec"))
        keys = PSUM_CONTRACTS
    else:
        view = ProgramView(spec, overrides=overrides, wrap=wrap)
        view._variants = variants
        view._pinned = frozenset(pinned) if pinned is not None else None
        keys = set(CONTRACTS) - PSUM_CONTRACTS
    findings = []
    for c in CONTRACTS.values():
        if c.key not in keys:
            continue
        if families and c.family not in families:
            continue
        try:
            problems = list(c.check(view) or ())
        except Exception as e:  # noqa: BLE001 — a crashed check IS a finding
            findings.append(Finding(c.key, "error", spec.name,
                                    f"contract check crashed: "
                                    f"{type(e).__name__}: {e}",
                                    {"crashed": True}))
            continue
        for msg, details in problems:
            findings.append(Finding(c.key, c.severity, spec.name, msg,
                                    details))
    return findings


def check_all(names: Optional[Iterable[str]] = None,
              families: Optional[Iterable[str]] = None):
    """`check_contracts` over every registered step + psum spec."""
    specs = STEP_SPECS + PSUM_SPECS
    if names:
        specs = tuple(get_spec(n) for n in names)
    out = []
    for s in specs:
        out.extend(check_contracts(s, families=families))
    return out


def summary_table(findings, configs=None) -> str:
    """Fixed-width per-config x per-family error/warn table (the text the
    CLI and `examples/quantized_comm_demo.py` print)."""
    families = sorted({c.family for c in CONTRACTS.values()})
    if configs is None:
        configs = sorted({f.config for f in findings} |
                         {s.name for s in STEP_SPECS + PSUM_SPECS})
    by = {}
    for f in findings:
        by.setdefault((f.config, f.family), []).append(f)
    width = max([len(c) for c in configs] + [6])
    head = "config".ljust(width) + "".join(f"  {fam:>9}" for fam in families)
    lines = [head, "-" * len(head)]
    for cfg in configs:
        row = cfg.ljust(width)
        for fam in families:
            fs = by.get((cfg, fam), [])
            ne = sum(1 for f in fs if f.severity == "error")
            nw = sum(1 for f in fs if f.severity == "warn")
            cell = "ok" if not fs else \
                "/".join(filter(None, [f"{ne}E" if ne else "",
                                       f"{nw}W" if nw else ""])) or "info"
            row += f"  {cell:>9}"
        lines.append(row)
    return "\n".join(lines)
