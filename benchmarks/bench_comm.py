"""Paper Fig 5: communication overheads vs quantization case/size, with test
accuracy — the pdADMM-G-Q headline (up to ~45-50% reduction, no accuracy
loss). Wire bytes come from the CommLedger (repro.comm) — the single source
of truth every payload is recorded in — instead of a closed-form estimate.

Beyond the paper's fixed 8/16-bit cases, the `adaptive` row runs the
AdaQP-style residual-driven bit-width controller over ALL three exchanges
(q/p on their optimization grids, u on a per-payload affine wire — fp32 in
the paper and in every fixed case) under a global byte budget of 75% of the
fixed-8-bit spend: 8-bit wire while residuals are near their peak,
graduating to 16 bits as convergence tightens — strictly more saving than
the fixed-8-bit case, at equal or better accuracy.

The `overlap` row measures the OTHER half of the comm win (AdaQP's insight:
hide the latency, don't just shrink the message): distributed step wall time
with the double-buffered boundary exchange on vs off, plus the
ppermute-schedule introspection (carried in-flight starts / solve work
between issue and consume) proving the messages left the critical path.

The `allreduce` row makes the quantized psum PHYSICAL: int32 code-sum psum
vs the gather-based packed all-reduce (int4 nibbles in a uint8 container)
at 8 simulated CPU devices — wall time plus ledger-verified wire bytes
(gather ships < 1/4 of the int32 path at int4), decode bit-identity
asserted in-run. The `mixed_width` row runs the padded-container wire under
the per-boundary controller: n_compiled_steps (exactly 1 across every
schedule) and active-codec bytes saved vs pinning every boundary to the
widest width.

The `costmodel` row closes the loop on wall time: the trace-driven replay
model (repro.analysis.replay) is calibrated from micro-runs, predicts the
overlap on/off step pair (relative error + ordering recorded), and prices
the walltime-objective controller's schedules against the bytes floor on
the mixed-width bench. The `control_interval` row sweeps the adaptive
loop's schedule-lag vs host-sync tradeoff at interval ∈ {1, 4, 16}.

The `faults` row prices fault tolerance (repro.comm.faults): step-time
overhead of the integrity-header sentinels, objective/step-time degradation
under injected wire bit-flips at rate ∈ {0, 0.05, 0.2} (every one detected
and recovered in-step off the last-good slabs), and the wall-clock cost of
a checkpoint rollback when sneaky corruption slips past the header.

Distributed rows run in a subprocess with 8 forced CPU devices so the
device-count flag never leaks into this process; `--smoke` runs every row
at small shapes and writes BENCH_comm.json (the CI bench-smoke artifact).
"""
from __future__ import annotations

import argparse
import json
import subprocess
import sys
from pathlib import Path

import jax

from benchmarks.common import DATASET_SCALES, print_rows, write_csv
from repro.comm import BitWidthController, CommLedger, ControllerConfig
from repro.comm.codecs import FP32, codec_for_grid
from repro.comm.controller import admm_edges, train_adaptive
from repro.comm.ledger import admm_bytes_per_iteration, record_admm_iteration
from repro.core import pdadmm
from repro.core.pdadmm import ADMMConfig
from repro.graph.datasets import synthetic

DATASETS = ["citeseer", "pubmed", "coauthor_cs"]

CASES = [
    ("none", 32, False, False),
    ("p_16bit", 16, True, False),
    ("p_8bit", 8, True, False),
    ("pq_16bit", 16, True, True),
    ("pq_8bit", 8, True, True),
]

ADAPTIVE_BITS = (8, 16)


def _run_fixed(case, bits, qp, qq, X, ds, dims, epochs):
    grid = pdadmm.calibrate_grid(jax.random.PRNGKey(0), X, dims,
                                 bits) if qp else None
    cfg = ADMMConfig(nu=1e-2, rho=1.0, quantize_p=qp, quantize_q=qq,
                     grid=grid)
    ledger = CommLedger()
    p_codec = codec_for_grid(grid if qp else None)
    q_codec = codec_for_grid(grid if qq else None)
    V = X.shape[0]
    _, hist = pdadmm.train(
        jax.random.PRNGKey(0), X, ds.labels, ds.masks, dims, cfg,
        epochs=epochs,
        callback=lambda e, s, m: record_admm_iteration(
            ledger, e, dims, V, p_codec, q_codec, FP32))
    return ledger, hist


def _run_adaptive(X, ds, dims, epochs):
    V = X.shape[0]
    key = jax.random.PRNGKey(0)
    grids = {b: pdadmm.calibrate_grid(key, X, dims, b)
             for b in ADAPTIVE_BITS}
    # manage p/q AND u exchanges; never below 8 bits (the accuracy-safe
    # floor), win bytes by keeping most iterations at 8 and graduating to 16
    # as residuals contract. Budget: 75% of the fixed-8-bit TOTAL spend
    # (which includes u at fp32), i.e. strictly better than the paper's
    # best fixed case by construction.
    edges = admm_edges(dims, V)
    # fixed-8-bit reference spend, from the ledger (the single source of
    # truth for wire bytes — never a side formula)
    fixed8_total = epochs * admm_bytes_per_iteration(
        dims, V, codec_for_grid(grids[8]), codec_for_grid(grids[8]), FP32)
    controller = BitWidthController(edges, ControllerConfig(
        allowed_bits=ADAPTIVE_BITS, min_bits=8, max_bits=16,
        byte_budget=0.75 * fixed8_total, total_iters=epochs))
    ledger = CommLedger()
    cfg = ADMMConfig(nu=1e-2, rho=1.0)
    _, hist = train_adaptive(key, X, ds.labels, ds.masks, dims, cfg, epochs,
                             controller=controller, ledger=ledger,
                             grids_by_bits=grids)
    return ledger, hist, controller


ROOT = Path(__file__).resolve().parents[1]

_OVERLAP_SNIPPET = """
import os, json, time
# the forced device count only applies to the CPU backend — pin it so the
# 8-device mesh exists even on accelerator hosts
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src"); sys.path.insert(0, "tests")
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.analysis.jaxpr_tools import collective_profile
from repro.launch.mesh import make_mesh
from repro.core.pdadmm import ADMMConfig
from repro.core import quantize
from repro.comm.codecs import codec_for_grid
from repro.parallel import stage_parallel as SP

V, h, L, C, iters = %(V)d, %(h)d, %(L)d, 4, %(iters)d
mesh = make_mesh((2, 4), ("data", "model"))
cfg = ADMMConfig(nu=1e-2, rho=1.0, quantize_p=True, quantize_q=True,
                 grid=quantize.uniform_grid(8, -2.0, 6.0))
key = jax.random.PRNGKey(0)
Xp = jax.random.normal(key, (V, h))
state0 = SP.init_stack(key, Xp, L, cfg)
specs = SP.stack_partition_specs(mesh)
put = lambda x, s: jax.device_put(x, NamedSharding(mesh, s))
state0 = jax.tree.map(put, state0, specs)
args = (put(Xp, P("data")), put(jnp.zeros((V,), jnp.int32), P("data")),
        put(jnp.ones((V,)), P("data")))

def run(overlap):
    step, _ = SP.make_distributed_step(mesh, L, C, cfg, overlap=overlap)
    carry = state0
    if overlap:
        primer = SP.make_overlap_primer(mesh, codec_for_grid(cfg.grid))
        carry = (state0, primer(state0.q, state0.u))
    carry, _m = step(carry, *args)            # compile + warmup
    jax.block_until_ready(carry)
    t0 = time.perf_counter()
    for _ in range(iters):
        carry, _m = step(carry, *args)
    jax.block_until_ready(carry)
    ms = (time.perf_counter() - t0) / iters * 1e3
    prof = collective_profile(jax.make_jaxpr(step)(carry, *args).jaxpr)
    return ms, prof

base_ms, base_prof = run(False)
ov_ms, ov_prof = run(True)
print(json.dumps({
    "V": V, "h": h, "L": L, "iters": iters,
    "baseline_step_ms": round(base_ms, 3),
    "overlap_step_ms": round(ov_ms, 3),
    "baseline_carried_ppermutes": sum(p["carried"] for p in base_prof),
    "overlap_carried_ppermutes": sum(p["carried"] for p in ov_prof),
    "overlap_p_work_to_consumer": max(
        (p["work_to_consumer"] for p in ov_prof if not p["carried"]),
        default=0),
}))
"""


def bench_overlap(smoke: bool = False):
    """Step wall time with the double-buffered boundary exchange on/off on
    8 simulated CPU devices (latency hiding needs real ICI to show its full
    win — the schedule introspection is the hardware-independent proof that
    the ppermutes moved), written to BENCH_comm.json."""
    V, h, L, iters = (128, 32, 8, 10) if smoke else (512, 64, 8, 30)
    code = _OVERLAP_SNIPPET % {"V": V, "h": h, "L": L, "iters": iters}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=ROOT, timeout=900)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    data = json.loads(r.stdout.strip().splitlines()[-1])
    assert data["overlap_carried_ppermutes"] == 2, data    # knob is real
    assert data["baseline_carried_ppermutes"] == 0, data
    header = ["case", "step_ms", "carried_ppermutes", "p_work_to_consumer"]
    rows = [
        ["exchange_fused", data["baseline_step_ms"],
         data["baseline_carried_ppermutes"], 0],
        ["exchange_overlap", data["overlap_step_ms"],
         data["overlap_carried_ppermutes"],
         data["overlap_p_work_to_consumer"]],
    ]
    write_csv("comm_overlap", header, rows)
    print_rows("comm_overlap (double-buffered boundary exchange)", header,
               rows)
    return data


_ALLREDUCE_SNIPPET = """
import os, json, time
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.comm import CommLedger, transport
from repro.comm.codecs import GridCodec
from repro.core.quantize import uniform_grid
from repro.comm.transport import record_psum

W, V, h, iters = 8, %(V)d, %(h)d, %(iters)d
mesh = make_mesh((W,), ("data",))
codec = GridCodec(uniform_grid(4, -3.0, 3.0))
x = jax.random.normal(jax.random.PRNGKey(0), (W * V, h))

def run(mode):
    def f(xx):
        return transport.quantized_psum(xx, "data", codec, mode=mode)
    sm = jax.jit(shard_map(f, mesh=mesh, in_specs=(P("data"),),
                           out_specs=P("data"), check_vma=False))
    y = sm(x); jax.block_until_ready(y)         # compile + warmup
    t0 = time.perf_counter()
    for _ in range(iters):
        y = sm(x)
    jax.block_until_ready(y)
    ms = (time.perf_counter() - t0) / iters * 1e3
    led = CommLedger()                          # ledger-verified, per shard
    cost = record_psum(led, 0, "allreduce", codec, (V, h), W, mode=mode)
    return ms, led.total_wire_bytes(), led.total_bytes(), np.asarray(y), cost

g_ms, g_wire, g_logical, g_y, g_cost = run("gather")
c_ms, c_wire, c_logical, c_y, c_cost = run("code_psum")
assert np.array_equal(g_y, c_y)                 # bit-identical decode
assert transport.psum_mode(codec, W) == "gather"
assert g_wire < 0.25 * c_wire, (g_wire, c_wire) # the acceptance bar
print(json.dumps({
    "world": W, "elements": V * h, "bits": codec.bits, "iters": iters,
    "gather_ms": round(g_ms, 3), "code_psum_ms": round(c_ms, 3),
    "gather_wire_bytes": int(g_wire), "code_psum_wire_bytes": int(c_wire),
    "logical_bytes": int(g_logical),
    "wire_ratio": round(g_wire / c_wire, 4),
    "selected_mode": transport.psum_mode(codec, W),
    "bit_identical": True,
}))
"""


def bench_allreduce(smoke: bool = False):
    """int32 code-sum psum vs gather-based packed all-reduce for an int4
    codec at 8 simulated CPU devices: wall time + LEDGER-verified physical
    bytes (the packed uint8 container vs the int32 message each shard
    injects), decode bit-identity asserted in-run."""
    V, h, iters = (256, 32, 20) if smoke else (2048, 64, 50)
    code = _ALLREDUCE_SNIPPET % {"V": V, "h": h, "iters": iters}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=ROOT, timeout=900)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    data = json.loads(r.stdout.strip().splitlines()[-1])
    header = ["path", "wall_ms", "wire_bytes_per_shard", "logical_bytes"]
    rows = [
        ["code_psum_int32", data["code_psum_ms"],
         data["code_psum_wire_bytes"], data["logical_bytes"]],
        ["gather_packed_int4", data["gather_ms"],
         data["gather_wire_bytes"], data["logical_bytes"]],
    ]
    write_csv("comm_allreduce", header, rows)
    print_rows("comm_allreduce (physical quantized all-reduce, int4 @ 8 "
               "devices)", header, rows)
    return data


_MIXED_SNIPPET = """
import os, json
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core.pdadmm import ADMMConfig
from repro.core import quantize
from repro.comm import BitWidthController, CommLedger, ControllerConfig
from repro.comm.controller import stage_ring_edges
from repro.graph.datasets import tiny
from repro.parallel import stage_parallel as SP

V, h, L, epochs = %(V)d, %(h)d, %(L)d, %(epochs)d
mesh = make_mesh((2, 4), ("data", "model"))
n_stages = 4
ds = tiny(V=V)
X = ds.augmented(4)
key = jax.random.PRNGKey(0)
P0 = jax.random.normal(key, (X.shape[1], h)) * jnp.sqrt(2.0 / X.shape[1])
Xp = jnp.maximum(X @ P0, 0)
grids = {b: quantize.uniform_grid(b, -2.0, 6.0) for b in (4, 8, 16)}
ctl = BitWidthController(
    stage_ring_edges(n_stages, V, h),
    ControllerConfig(allowed_bits=(4, 8, 16), min_bits=4, max_bits=16,
                     min_dwell=1, hysteresis=0.0, signal="per_edge",
                     thresholds=((0.5, 4), (0.1, 8))))
led = CommLedger()
cfg = ADMMConfig(nu=1e-2, rho=1.0)
_, hist = SP.distributed_train(mesh, key, Xp, ds.labels, ds.masks, L,
                               ds.n_classes, cfg, epochs=epochs,
                               controller=ctl, grids_by_bits=grids,
                               ledger=led, mixed_width=True)
assert hist["n_compiled_steps"] == 1, hist["n_compiled_steps"]
wire = SP.PaddedWire.from_grids(grids)
uniform = epochs * (
    2 * sum(SP.container_wire_bytes_per_iteration(
        mesh, L, V, h, wire, (wire.widest,) * n_stages,
        (wire.widest,) * n_stages)["q_fwd"]))
mixed = sum(v for e, v in led.per_edge().items()
            if e.startswith(("q_fwd/s", "p_bwd/s")))
print(json.dumps({
    "epochs": epochs, "n_stages": n_stages,
    "n_compiled_steps": hist["n_compiled_steps"],
    "n_distinct_schedules": len(set(hist["schedules"])),
    "mixed_pq_logical_bytes": int(mixed),
    "uniform_widest_pq_bytes": int(uniform),
    "bytes_saved_vs_uniform": round(1 - mixed / uniform, 4),
    "container_wire_bytes": int(sum(
        v for e, v in led.per_edge_wire().items()
        if e.startswith(("q_fwd/s", "p_bwd/s")))),
}))
"""


def bench_mixed_width(smoke: bool = False):
    """Per-boundary mixed bit-widths through the padded-container wire:
    n_compiled_steps (exactly 1 across every schedule the controller emits)
    and active-codec bytes saved vs running every boundary at the widest
    width — the schedule the single-format SPMD step would otherwise be
    pinned to."""
    V, h, L, epochs = (64, 32, 8, 10) if smoke else (256, 64, 8, 30)
    code = _MIXED_SNIPPET % {"V": V, "h": h, "L": L, "epochs": epochs}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=ROOT, timeout=900)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    data = json.loads(r.stdout.strip().splitlines()[-1])
    assert data["n_compiled_steps"] == 1, data
    header = ["n_compiled_steps", "n_distinct_schedules",
              "mixed_pq_logical_bytes", "uniform_widest_pq_bytes",
              "bytes_saved_vs_uniform"]
    rows = [[data[k] for k in header]]
    write_csv("comm_mixed_width", header, rows)
    print_rows("comm_mixed_width (padded containers, one compiled step)",
               header, rows)
    return data


_COSTMODEL_SNIPPET = """
import os, json, time
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
# the replay model is calibrated and validated in the interpret-kernel
# regime: its per-op overhead dominates the CPU-sim step, which makes the
# measured pair stable run-to-run (the ref-mode pair is noise-level on a
# time-sliced single core)
os.environ["REPRO_KERNELS"] = "interpret"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.core.pdadmm import ADMMConfig
from repro.core import quantize
from repro.comm import BitWidthController, CommLedger, ControllerConfig
from repro.comm.codecs import codec_for_grid
from repro.comm.controller import stage_ring_edges
from repro.graph.datasets import tiny
from repro.parallel import stage_parallel as SP
from repro.analysis.replay import calibrate, replay

V, h, L, C, iters, epochs = %(V)d, %(h)d, %(L)d, 4, %(iters)d, %(epochs)d
mesh = make_mesh((2, 4), ("data", "model"))
n_stages = 4
cfg = ADMMConfig(nu=1e-2, rho=1.0, quantize_p=True, quantize_q=True,
                 grid=quantize.uniform_grid(8, -2.0, 6.0))
key = jax.random.PRNGKey(0)
Xp = jax.random.normal(key, (V, h))
state0 = SP.init_stack(key, Xp, L, cfg)
specs = SP.stack_partition_specs(mesh)
put = lambda x, s: jax.device_put(x, NamedSharding(mesh, s))
state0 = jax.tree.map(put, state0, specs)
args = (put(Xp, P("data")), put(jnp.zeros((V,), jnp.int32), P("data")),
        put(jnp.ones((V,)), P("data")))

costs = calibrate(mesh, V=V, h=h)
out = {"V": V, "h": h, "L": L, "iters": iters,
       "kernels": os.environ["REPRO_KERNELS"]}

# predicted vs measured, overlap off/on -------------------------------------
for overlap in (False, True):
    step, _ = SP.make_distributed_step(mesh, L, C, cfg, overlap=overlap)
    carry = state0
    if overlap:
        primer = SP.make_overlap_primer(mesh, codec_for_grid(cfg.grid))
        carry = (state0, primer(state0.q, state0.u))
    carry, _m = step(carry, *args)            # compile + warmup
    jax.block_until_ready(carry)
    t0 = time.perf_counter()
    for _ in range(iters):
        carry, _m = step(carry, *args)
    jax.block_until_ready(carry)
    ms = (time.perf_counter() - t0) / iters * 1e3
    dag = SP.trace_step_dag(mesh, L, C, cfg, V=V, h=h, overlap=overlap)
    pred = replay(dag, costs).step_time_ms
    k = "overlap" if overlap else "baseline"
    out[k + "_measured_ms"] = round(ms, 3)
    out[k + "_predicted_ms"] = round(pred, 3)
    out[k + "_rel_err"] = round(abs(pred - ms) / ms, 4)
out["predicted_ordering_ok"] = bool(
    out["overlap_predicted_ms"] <= out["baseline_predicted_ms"])

# walltime- vs bytes-objective controller on the mixed-width bench ----------
ds = tiny(V=V)
X = ds.augmented(4)
P0 = jax.random.normal(key, (X.shape[1], h)) * jnp.sqrt(2.0 / X.shape[1])
Xp2 = jnp.maximum(X @ P0, 0)
grids = {b: quantize.uniform_grid(b, -2.0, 6.0) for b in (4, 8, 16)}
cm = SP.step_cost_model(mesh, L, C, cfg, costs, V=V, h=h,
                        grids_by_bits=grids, mixed_width=True)
ctl_kw = dict(allowed_bits=(4, 8, 16), min_bits=4, max_bits=16,
              min_dwell=1, hysteresis=0.0, signal="per_edge",
              thresholds=((0.5, 4), (0.1, 8)))
trained = {}
for name, cc, cmod in (
        ("bytes", ControllerConfig(**ctl_kw), None),
        ("walltime", ControllerConfig(objective="walltime", **ctl_kw), cm)):
    ctl = BitWidthController(stage_ring_edges(n_stages, V, h), cc,
                             cost_model=cmod)
    led = CommLedger()
    _, hist = SP.distributed_train(
        mesh, key, Xp2, ds.labels, ds.masks, L, ds.n_classes,
        ADMMConfig(nu=1e-2, rho=1.0), epochs=epochs, controller=ctl,
        grids_by_bits=grids, ledger=led, mixed_width=True)
    assert hist["n_compiled_steps"] == 1, hist["n_compiled_steps"]
    trained[name] = hist
    out[name + "_final_schedule"] = list(hist["schedules"][-1])
    out[name + "_n_distinct_schedules"] = len(set(hist["schedules"]))
    out[name + "_n_compiled_steps"] = hist["n_compiled_steps"]
    out[name + "_predicted_step_ms"] = round(
        cm(hist["schedules"][-1]) * 1e3, 3)
# the walltime objective may never emit a schedule predicted slower than
# the bytes floor of the SAME iteration
assert all(cm(w) <= cm(b) * (1 + 1e-9) for b, w in
           zip(trained["bytes"]["schedules"],
               trained["walltime"]["schedules"]))
out["walltime_never_slower"] = True
print(json.dumps(out))
"""


def bench_costmodel(smoke: bool = False):
    """The replay cost model against reality, at 8 simulated CPU devices:
    calibrate from micro-runs (never from the step under test), predict the
    overlap on/off step pair, and report relative error + predicted
    ordering. Then run the mixed-width bench under a bytes-objective and a
    walltime-objective controller sharing one ScheduleCostModel: the
    walltime schedules must never be predicted slower than the bytes floor,
    with no compile blowup (the container path's single compiled step)."""
    V, h, L, iters, epochs = ((128, 32, 8, 10, 6) if smoke
                              else (128, 32, 8, 30, 12))
    code = _COSTMODEL_SNIPPET % {"V": V, "h": h, "L": L, "iters": iters,
                                 "epochs": epochs}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=ROOT, timeout=900)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    data = json.loads(r.stdout.strip().splitlines()[-1])
    assert data["predicted_ordering_ok"], data
    header = ["case", "measured_ms", "predicted_ms", "rel_err"]
    rows = [
        ["exchange_fused", data["baseline_measured_ms"],
         data["baseline_predicted_ms"], data["baseline_rel_err"]],
        ["exchange_overlap", data["overlap_measured_ms"],
         data["overlap_predicted_ms"], data["overlap_rel_err"]],
    ]
    write_csv("comm_costmodel", header, rows)
    print_rows("comm_costmodel (replay prediction vs measured, interpret "
               "kernels)", header, rows)
    print(f"  walltime controller: final schedule "
          f"{data['walltime_final_schedule']} predicted "
          f"{data['walltime_predicted_step_ms']} ms vs bytes "
          f"{data['bytes_final_schedule']} predicted "
          f"{data['bytes_predicted_step_ms']} ms")
    return data


def bench_control_interval(smoke: bool = False):
    """ROADMAP follow-up: the `control_interval` schedule-lag vs host-sync
    tradeoff. One adaptive run per interval in {1, 4, 16} (fresh controller
    and ledger each) — an interval-k run makes epochs/k host syncs and the
    controller reacts to residuals up to k-1 iterations stale; bytes and
    accuracy quantify what that staleness costs."""
    from repro.graph.datasets import tiny
    V, hidden, layers, epochs = ((64, 32, 4, 16) if smoke
                                 else (256, 64, 6, 32))
    ds = tiny(V=V)
    X = ds.augmented(4)
    dims = [X.shape[1]] + [hidden] * (layers - 1) + [ds.n_classes]
    key = jax.random.PRNGKey(0)
    grids = {b: pdadmm.calibrate_grid(key, X, dims, b)
             for b in ADAPTIVE_BITS}
    cfg = ADMMConfig(nu=1e-2, rho=1.0)
    out = {"V": V, "epochs": epochs, "intervals": {}}
    rows = []
    for interval in (1, 4, 16):
        # reactive config (single threshold, no dwell/hysteresis damping):
        # the schedule graduates 8 -> 16 the moment the summed residual
        # falls below half its peak, so interval lag is actually visible
        # in the bytes column instead of damped away
        controller = BitWidthController(
            admm_edges(dims, V),
            ControllerConfig(allowed_bits=ADAPTIVE_BITS, min_bits=8,
                             max_bits=16, thresholds=((0.5, 8),),
                             min_dwell=1, hysteresis=0.0))
        ledger = CommLedger()
        _, hist = train_adaptive(key, X, ds.labels, ds.masks, dims, cfg,
                                 epochs, controller=controller,
                                 ledger=ledger, grids_by_bits=grids,
                                 control_interval=interval)
        row = {"host_syncs": -(-epochs // interval),
               "total_bytes": int(ledger.total_bytes()),
               "n_switches": controller.n_switches,
               "test_acc": round(hist["test_acc"][-1], 4)}
        out["intervals"][str(interval)] = row
        rows.append([interval, row["host_syncs"], row["total_bytes"],
                     row["n_switches"], row["test_acc"]])
    header = ["control_interval", "host_syncs", "total_bytes", "n_switches",
              "test_acc"]
    write_csv("comm_control_interval", header, rows)
    print_rows("comm_control_interval (schedule lag vs host syncs)", header,
               rows)
    return out


_FAULTS_SNIPPET = """
import os, json, time
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import shutil, tempfile
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.core.pdadmm import ADMMConfig
from repro.comm import faults as F
from repro.comm.ledger import CommLedger
from repro.parallel import stage_parallel as SP

V, h, L, C, epochs = %(V)d, %(h)d, %(L)d, 4, %(epochs)d
mesh = make_mesh((2, 4), ("data", "model"))
key = jax.random.PRNGKey(0)
Xp = jax.random.normal(key, (V, h))
labels = jax.random.randint(jax.random.PRNGKey(1), (V,), 0, C)
masks = {"train": jnp.ones((V,))}
cfg = ADMMConfig(nu=1.0, rho=1.0)
out = {"V": V, "h": h, "L": L, "epochs": epochs}

def timed(**kw):
    t0 = time.perf_counter()
    _, hist = SP.distributed_train(mesh, key, Xp, labels, masks, L, C, cfg,
                                   epochs, **kw)
    return (time.perf_counter() - t0) * 1e3 / epochs, hist

# sentinel overhead: the +8 B header pair and verdict logic per exchange.
# Every case below pays one compile inside its own distributed_train call,
# so the per-epoch numbers are comparable case-to-case (not compile-free).
base_ms, base_hist = timed()
sent_ms, sent_hist = timed(health=True)
out["plain_step_ms"] = round(base_ms, 3)
out["sentinel_step_ms"] = round(sent_ms, 3)
out["sentinel_overhead"] = round(sent_ms / base_ms - 1, 4)
out["clean_objective"] = round(base_hist["objective"][-1], 4)
assert sent_hist["objective"] == base_hist["objective"]  # identity, again

# chaos degradation sweep: objective + step time vs flip rate (in-step
# recovery only — detected flips are replaced by the last good slab)
out["flip_sweep"] = {}
for rate in (0.0, 0.05, 0.2):
    plan = F.FaultPlan(seed=1, flip_rate=rate)
    led = CommLedger()
    ms, hist = timed(faults=plan, ledger=led)
    f = hist["faults"]
    assert f["detected"] == f["recovered"], f
    out["flip_sweep"]["%%.2f" %% rate] = {
        "step_ms": round(ms, 3),
        "objective": round(hist["objective"][-1], 4),
        "degradation": round(hist["objective"][-1]
                             - base_hist["objective"][-1], 4),
        "injected": f["injected"], "recovered": f["recovered"],
    }

# rollback recovery: sneaky corruption past the header -> sentinel trips ->
# restore from checkpoint; recovery wall time is the chaos run's overhead
# over the clean run amortized per rollback
plan = F.FaultPlan(seed=11, sneaky_rate=0.08, flips_per_event=6)
d = tempfile.mkdtemp()
t0 = time.perf_counter()
_, hist = SP.distributed_train(mesh, key, Xp, labels, masks, L, C, cfg,
                               epochs, faults=plan, ckpt=d, ckpt_every=2)
chaos_ms = (time.perf_counter() - t0) * 1e3
shutil.rmtree(d)
n_rb = hist["faults"]["rolled_back"]
assert n_rb >= 1, hist["faults"]
clean_ms = base_ms * epochs
out["rollbacks"] = n_rb
out["rollback_recovery_ms"] = round(max(chaos_ms - clean_ms, 0.0) / n_rb, 3)
out["chaos_final_objective"] = round(hist["objective"][-1], 4)
print(json.dumps(out))
"""


def bench_faults(smoke: bool = False):
    """The PR-7 fault-tolerance row: sentinel (integrity-header) step
    overhead vs the plain step, objective/step-time degradation vs injected
    flip rate (all in-step recovered off the last-good slabs), and the
    wall-clock cost of a checkpoint rollback when sneaky corruption gets
    past the header and trips the objective/finite sentinels."""
    V, h, L, epochs = (64, 32, 8, 8) if smoke else (128, 32, 8, 20)
    code = _FAULTS_SNIPPET % {"V": V, "h": h, "L": L, "epochs": epochs}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=ROOT, timeout=900)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    data = json.loads(r.stdout.strip().splitlines()[-1])
    header = ["case", "step_ms", "final_objective", "injected", "recovered"]
    rows = [["plain", data["plain_step_ms"], data["clean_objective"], 0, 0],
            ["sentinel", data["sentinel_step_ms"], data["clean_objective"],
             0, 0]]
    for rate, row in sorted(data["flip_sweep"].items()):
        rows.append([f"flip_{rate}", row["step_ms"], row["objective"],
                     row["injected"], row["recovered"]])
    rows.append(["sneaky_rollback", "-", data["chaos_final_objective"],
                 "-", f"{data['rollbacks']} rollbacks @ "
                      f"{data['rollback_recovery_ms']} ms"])
    write_csv("comm_faults", header, rows)
    print_rows("comm_faults (wire chaos: sentinel overhead, flip sweep, "
               "rollback recovery)", header, rows)
    return data


def write_bench_json(**rows):
    (ROOT / "BENCH_comm.json").write_text(
        json.dumps(rows, indent=2) + "\n")


def run_smoke():
    write_bench_json(overlap=bench_overlap(smoke=True),
                     allreduce=bench_allreduce(smoke=True),
                     mixed_width=bench_mixed_width(smoke=True),
                     costmodel=bench_costmodel(smoke=True),
                     control_interval=bench_control_interval(smoke=True),
                     faults=bench_faults(smoke=True))


def run(epochs: int = 30, hidden: int = 100, layers: int = 10):
    rows = []
    for name in DATASETS:
        ds = synthetic(name, scale=min(DATASET_SCALES[name], 0.25))
        X = ds.augmented(4)
        dims = [X.shape[1]] + [hidden] * (layers - 1) + [ds.n_classes]
        base_bytes = None
        for case, bits, qp, qq in CASES:
            ledger, hist = _run_fixed(case, bits, qp, qq, X, ds, dims, epochs)
            total = ledger.total_bytes()
            if base_bytes is None:
                base_bytes = total
            rows.append([name, case, int(total),
                         f"{100 * (1 - total / base_bytes):.1f}%",
                         f"{hist['test_acc'][-1]:.3f}"])
        ledger, hist, controller = _run_adaptive(X, ds, dims, epochs)
        total = ledger.total_bytes()
        rows.append([name, "adaptive", int(total),
                     f"{100 * (1 - total / base_bytes):.1f}%",
                     f"{hist['test_acc'][-1]:.3f}"])
    header = ["dataset", "case", "total_comm_bytes", "saving_vs_fp32",
              "test_acc"]
    write_csv("fig5_comm_overheads", header, rows)
    print_rows("fig5_comm_overheads (paper Fig 5 + adaptive)", header, rows)
    write_bench_json(overlap=bench_overlap(),
                     allreduce=bench_allreduce(),
                     mixed_width=bench_mixed_width(),
                     costmodel=bench_costmodel(),
                     control_interval=bench_control_interval(),
                     faults=bench_faults())
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="overlap/allreduce/mixed_width/costmodel/"
                         "control_interval/faults rows only, small shapes "
                         "(CI artifact)")
    if ap.parse_args().smoke:
        run_smoke()
    else:
        run()
