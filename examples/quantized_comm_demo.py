"""Distributed stage-parallel pdADMM-G with a quantized ICI wire — runs the
shard_map runtime on 8 simulated devices and prints the HLO-level proof that
the int8 wire shrinks the collective-permute payloads (the paper's Fig 5
claim at the compiler level), then the offline replay cost model: predicted
vs measured step time for the overlap pair, and the schedule the
walltime-objective controller chooses through it.

  python examples/quantized_comm_demo.py       (sets its own XLA_FLAGS)
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
import sys
from pathlib import Path
sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp

from repro.analysis import hlo as H
from repro.comm import CommLedger
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.core import quantize
from repro.core.pdadmm import ADMMConfig
from repro.graph.datasets import tiny
from repro.parallel import stage_parallel as SP


def wire_bytes(mesh, cfg, V=256, h=64, L=8, C=4):
    step, _ = SP.make_distributed_step(mesh, L, C, cfg)
    st = jax.eval_shape(lambda k: SP.init_stack(k, jnp.zeros((V, h)), L, cfg),
                        jax.random.PRNGKey(0))
    lowered = step.lower(st, jax.ShapeDtypeStruct((V, h), jnp.float32),
                         jax.ShapeDtypeStruct((V,), jnp.int32),
                         jax.ShapeDtypeStruct((V,), jnp.float32))
    stats = H.analyze(lowered.compile().as_text(), 8)
    return stats.coll_summary()["by_kind"].get(
        "collective-permute", {"payload_bytes": 0})["payload_bytes"]


def main():
    enable_compile_cache()
    mesh = make_mesh((2, 4), ("data", "model"))
    fp = wire_bytes(mesh, ADMMConfig(nu=1e-2, rho=1.0))
    g8 = quantize.uniform_grid(8, -2.0, 6.0)
    q8 = wire_bytes(mesh, ADMMConfig(nu=1e-2, rho=1.0, quantize_p=True,
                                     quantize_q=True, grid=g8))
    print(f"collective-permute payload per iteration (per device):")
    print(f"  fp32 wire : {fp:10d} bytes")
    print(f"  int8 wire : {q8:10d} bytes  ({100*(1-q8/fp):.0f}% saved)")

    # and it still converges — with every payload on the CommLedger:
    ds = tiny(V=128)
    X = ds.augmented(4)
    key = jax.random.PRNGKey(0)
    P0 = jax.random.normal(key, (X.shape[1], 64)) * jnp.sqrt(2.0 / X.shape[1])
    Xp = jnp.maximum(X @ P0, 0)
    cfg = ADMMConfig(nu=1e-2, rho=1.0, quantize_p=True, quantize_q=True,
                     grid=g8)
    ledger = CommLedger()
    _, hist = SP.distributed_train(mesh, key, Xp, ds.labels, ds.masks, 8,
                                   ds.n_classes, cfg, epochs=15,
                                   ledger=ledger)
    print(f"quantized-wire objective: {hist['objective'][0]:.3f} -> "
          f"{hist['objective'][-1]:.3f} (residual {hist['residual'][-1]:.1e})")
    s = ledger.summary()
    print(f"ledger: {s['total_bytes']} wire bytes over {s['iterations']} "
          f"iters ({100 * s['savings_vs_fp32']:.0f}% saved vs fp32)")

    # the second half of the comm win: the same run with the boundary
    # exchange double-buffered (ppermutes issued an iteration early, carried
    # in-flight) — bitwise-identical trajectory, messages off the critical
    # path
    led_ov = CommLedger()
    _, hist_ov = SP.distributed_train(mesh, key, Xp, ds.labels, ds.masks, 8,
                                      ds.n_classes, cfg, epochs=15,
                                      ledger=led_ov, overlap=True)
    assert hist_ov["objective"] == hist["objective"]
    # consumed per-iteration traffic is identical; the overlap ledger also
    # charges the tail q/u pair still in flight at termination
    consumed = {e: b for e, b in led_ov.per_edge().items()
                if not e.endswith("/inflight")}
    assert consumed == ledger.per_edge()
    tail = led_ov.total_bytes() - ledger.total_bytes()
    print(f"overlap=True: identical trajectory, identical per-iteration "
          f"wire bytes (+{tail} B tail pair left in flight at termination)")

    # per-boundary MIXED bit-widths through the padded-container wire: the
    # controller assigns each stage boundary its own width every iteration
    # from the per-stage residuals, inside ONE compiled step — schedule
    # changes swap a traced widths table, never a compilation
    from repro.comm import BitWidthController, ControllerConfig
    from repro.comm.controller import stage_ring_edges
    grids = {b: quantize.uniform_grid(b, -2.0, 6.0) for b in (4, 8, 16)}
    n_stages = 4
    ctl = BitWidthController(
        stage_ring_edges(n_stages, Xp.shape[0], 64),
        ControllerConfig(allowed_bits=(4, 8, 16), min_bits=4, max_bits=16,
                         min_dwell=1, hysteresis=0.0, signal="per_edge",
                         thresholds=((0.5, 4), (0.1, 8))))
    led_mw = CommLedger()
    _, hist_mw = SP.distributed_train(
        mesh, key, Xp, ds.labels, ds.masks, 8, ds.n_classes,
        ADMMConfig(nu=1e-2, rho=1.0), epochs=15, controller=ctl,
        grids_by_bits=grids, ledger=led_mw, mixed_width=True)
    assert hist_mw["n_compiled_steps"] == 1
    print(f"mixed-width run: {len(set(hist_mw['schedules']))} distinct "
          f"per-boundary schedules (last: {hist_mw['schedules'][-1]}), "
          f"1 compiled step")
    s = led_mw.summary()
    print(f"  ledger: {s['total_bytes']} logical B (active codecs) vs "
          f"{s['wire_bytes']} physical B (padded containers on the link)")

    # offline replay cost model: calibrate link + compute rates from
    # micro-runs (never from the step under test), lift the jitted step's
    # jaxpr into a comm/compute DAG, and predict the stage-parallel step
    # time without running it
    import time
    from jax.sharding import NamedSharding, PartitionSpec as Pspec
    from repro.analysis.replay import calibrate, replay
    from repro.comm.codecs import codec_for_grid
    V, h, L = Xp.shape[0], Xp.shape[1], 8
    costs = calibrate(mesh, V=V, h=h)
    specs = SP.stack_partition_specs(mesh)
    put = lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp))
    st = jax.tree.map(put, SP.init_stack(key, Xp, L, cfg), specs)
    args = (put(Xp, Pspec("data")),
            put(jnp.zeros((V,), jnp.int32), Pspec("data")),
            put(jnp.ones((V,)), Pspec("data")))
    print("replay cost model: predicted vs measured step time")
    for overlap in (False, True):
        step, _ = SP.make_distributed_step(mesh, L, ds.n_classes, cfg,
                                           overlap=overlap)
        carry = st
        if overlap:
            primer = SP.make_overlap_primer(mesh, codec_for_grid(cfg.grid))
            carry = (st, primer(st.q, st.u))
        carry, _m = step(carry, *args)          # compile + warmup
        jax.block_until_ready(carry)
        t0 = time.perf_counter()
        for _ in range(5):
            carry, _m = step(carry, *args)
        jax.block_until_ready(carry)
        ms = (time.perf_counter() - t0) / 5 * 1e3
        dag = SP.trace_step_dag(mesh, L, ds.n_classes, cfg, V=V, h=h,
                                overlap=overlap)
        pred = replay(dag, costs).step_time_ms
        print(f"  overlap={str(overlap):5s}: measured {ms:7.2f} ms   "
              f"predicted {pred:7.2f} ms")
    print(f"  replay-searched choice: overlap="
          f"{SP.choose_overlap_for(mesh, L, ds.n_classes, cfg, V=V, h=h, costs=costs)}")

    # the same model drives the controller: objective="walltime" keeps the
    # residual-driven accuracy floor and promotes any boundary whose finer
    # width replay predicts costs no wall-time — on the padded-container
    # wire every promotion is free (the link carries the capacity either
    # way), so the replay-chosen schedule rides at the widest legal width
    cm = SP.step_cost_model(mesh, L, ds.n_classes, cfg, costs, V=V, h=h,
                            grids_by_bits=grids, mixed_width=True)
    ctl_wt = BitWidthController(
        stage_ring_edges(n_stages, V, h),
        ControllerConfig(objective="walltime", allowed_bits=(4, 8, 16),
                         min_bits=4, max_bits=16, min_dwell=1,
                         hysteresis=0.0, signal="per_edge",
                         thresholds=((0.5, 4), (0.1, 8))),
        cost_model=cm)
    _, hist_wt = SP.distributed_train(
        mesh, key, Xp, ds.labels, ds.masks, 8, ds.n_classes,
        ADMMConfig(nu=1e-2, rho=1.0), epochs=15, controller=ctl_wt,
        grids_by_bits=grids, ledger=CommLedger(), mixed_width=True)
    assert hist_wt["n_compiled_steps"] == 1
    sb, sw = hist_mw["schedules"][-1], hist_wt["schedules"][-1]
    print(f"walltime objective: bytes floor {tuple(sb)} -> replay-chosen "
          f"{tuple(sw)} ({cm(sb) * 1e3:.2f} -> {cm(sw) * 1e3:.2f} ms "
          f"predicted), still 1 compiled step")

    # chaos on the wire: a blackout silences every slab stage 2 sends for
    # two iterations, random bit-flips corrupt payloads in flight, and
    # sneaky (pre-checksum) corruption occasionally slips past the header.
    # The int32[2] checksum/seqno header riding next to each payload
    # detects the flips and the blackout drops — the step substitutes the
    # last good slab and keeps going (inexact updates are ADMM-legal) —
    # while anything the header can't see trips the objective/finite
    # sentinels and rolls the run back to the latest checkpoint. Same
    # quantized wire, still one compiled step.
    import shutil
    import tempfile
    from repro.comm import faults as FT
    plan = FT.FaultPlan(seed=11, flip_rate=0.05, sneaky_rate=0.04,
                        flips_per_event=6, blackouts=((2, 5, 2),))
    led_ft = CommLedger()
    d_ck = tempfile.mkdtemp()
    _, hist_ft = SP.distributed_train(mesh, key, Xp, ds.labels, ds.masks, 8,
                                      ds.n_classes, cfg, epochs=15,
                                      faults=plan, ledger=led_ft,
                                      ckpt=d_ck, ckpt_every=3)
    shutil.rmtree(d_ck)
    f = hist_ft["faults"]
    assert hist_ft["n_compiled_steps"] == 1
    print(f"chaos run (flips + stage-2 blackout + sneaky corruption): "
          f"{f['injected']} faults injected, {f['detected']} wire-detected, "
          f"{f['recovered']} recovered in-step, {f['rolled_back']} "
          f"rollback(s) to checkpoint")
    print(f"  objective {hist_ft['objective'][0]:.3f} -> "
          f"{hist_ft['objective'][-1]:.3f} under chaos "
          f"(clean run reached {hist['objective'][-1]:.3f}); "
          f"per-edge faults: {led_ft.fault_counts()}")

    # every claim above is also a standing contract: the static linter
    # re-derives dispatch/schedule/wire/memory/dtype facts from the traced
    # programs alone (no execution) — same checks as `python -m
    # repro.analysis.lint --all` in CI, summarized here for a fast subset
    from repro.analysis import contracts as CT
    names = ["baseline", "overlap", "int8_wire", "psum_int8_w4"]
    findings = CT.check_all(names)
    print("\nprogram-contract lint (static — traced, never run):")
    print(CT.summary_table(findings, names))
    n_err = sum(1 for f in findings if f.severity == "error")
    print(f"  {n_err} error(s) across {len(names)} configs")


if __name__ == "__main__":
    main()
