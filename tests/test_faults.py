"""Fault-tolerance battery for the PR-7 chaos/recovery subsystem
(repro.comm.faults + the sentinel step and FT loop in stage_parallel):

- FaultPlan determinism and class exclusivity at the host level (pure
  numpy, no devices): same seed => same trace, one verdict per slab.
- The wire integrity primitives: checksum/seqno header detects every
  non-sneaky flip (float payloads AND the packed uint8 gather containers
  the quantized psum ships), flip_bits is a bit-exact identity when
  inactive.
- No-fault identity: health=True and a zero-rate FaultPlan run the exact
  same numbers as the plain step — state, metrics, objective — and the
  ledger's LOGICAL accounting is untouched (headers are physical-only).
- Exact accounting: every injected wire fault produces exactly one failed
  verdict per data-parallel ring; chaos runs are bitwise-deterministic.
- Recovery acceptance: a seeded sneaky plan forces rollback-to-checkpoint
  and the run still converges; resume= continues from disk, including
  ELASTIC restore onto a different mesh shape.
- CheckpointManager sweeps stale `.tmp_*` staging dirs on construction.

Multi-device cases run in subprocesses with 8 forced CPU devices (the
main pytest process is locked to 1 device)."""
import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]


def _run(code: str) -> str:
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=ROOT, timeout=540)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    return r.stdout


PRELUDE = """
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.core.pdadmm import ADMMConfig
from repro.parallel import stage_parallel as SP
from repro.comm import faults as F
from repro.comm.ledger import CommLedger
mesh = make_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4])
n_stages, dp_total = 2, 2
V, h, L, C = 32, 8, 4, 3
key = jax.random.PRNGKey(0)
Xp = jax.random.normal(key, (V, h))
labels = jax.random.randint(jax.random.PRNGKey(1), (V,), 0, C)
masks = {"train": jnp.ones((V,))}
cfg = ADMMConfig(nu=1.0, rho=1.0, fista_iters=3)
"""


# --- host-level plan semantics (no devices) ---------------------------------


def test_fault_plan_deterministic_and_exclusive():
    from repro.comm.faults import EDGES, FaultPlan
    plan_a = FaultPlan(seed=5, flip_rate=0.2, sneaky_rate=0.2, drop_rate=0.2,
                       delay_rate=0.2, blackouts=((1, 3, 2),))
    plan_b = FaultPlan(seed=5, flip_rate=0.2, sneaky_rate=0.2, drop_rate=0.2,
                       delay_rate=0.2, blackouts=((1, 3, 2),))
    # pure function of (seed, tick): two instances, one schedule
    assert plan_a.trace(20, 4) == plan_b.trace(20, 4)
    assert plan_a.trace(20, 4) != FaultPlan(
        seed=6, flip_rate=0.2, sneaky_rate=0.2, drop_rate=0.2,
        delay_rate=0.2).trace(20, 4)
    ev = plan_a.trace(50, 4)
    assert ev, "rates this high must inject something in 50 ticks"
    assert {k for (_, _, _, k) in ev} == {"drop", "flip", "sneaky", "delay"}
    # exclusivity: at most ONE wire-verdict class (drop > flip > sneaky)
    # per (tick, edge, src slab). A delay may share its injection tick —
    # its verdict lands a tick LATER (stale seqno) and shadows that next
    # tick's q/u faults instead — but never rides on a dropped slab.
    for t in range(50):
        per_slab = {}
        for (e, s, k) in plan_a.events(t, 4):
            per_slab.setdefault((e, s), []).append(k)
        for slab, kinds in per_slab.items():
            wire = [k for k in kinds if k != "delay"]
            assert len(wire) <= 1, (t, slab, kinds)
            assert not ("delay" in kinds and "drop" in kinds), (t, slab)
        shadowed = plan_a._draw_delays(t, 4)
        for (e, s, k) in plan_a.events(t + 1, 4):
            if e in ("q_fwd", "u_fwd") and k != "delay":
                assert not shadowed[s], (t + 1, e, s, k)
    # blackout window: stage 1 drops on EVERY edge for ticks [3, 5) —
    # unless a prev-tick delay already claimed its q/u slabs' verdicts
    for t in (3, 4):
        got = {(e, s, k) for (e, s, k) in plan_a.events(t, 4) if s == 1}
        shadowed = plan_a._draw_delays(t - 1, 4)[1]
        want = {("p_bwd", 1, "drop")} if shadowed else {
            (e, 1, "drop") for e in EDGES}
        assert want <= got, (t, got)
    # zero-rate plan: inactive, and the schedule is empty
    assert not FaultPlan(seed=5).active
    assert FaultPlan(seed=5).trace(50, 4) == []
    assert plan_a.active


def test_fault_plan_controls_match_events():
    """The traced control block and the host-side event enumeration are two
    views of the same draw — accounting counts what the wire suffers."""
    from repro.comm.faults import EDGES, FaultPlan
    plan = FaultPlan(seed=9, flip_rate=0.15, drop_rate=0.15, sneaky_rate=0.1,
                     delay_rate=0.1)
    for t in range(30):
        ctl = plan.controls(t, 4)
        assert int(ctl.seqno) == t
        ev = plan.events(t, 4)
        for e_i, e_name in enumerate(EDGES):
            for s in range(4):
                assert bool(np.asarray(ctl.flip)[e_i, s]) == (
                    (e_name, s, "flip") in ev)
                assert bool(np.asarray(ctl.drop)[e_i, s]) == (
                    (e_name, s, "drop") in ev)
                assert bool(np.asarray(ctl.sneaky)[e_i, s]) == (
                    (e_name, s, "sneaky") in ev)
        for s in range(4):
            # a delay event fails BOTH forward slabs from that source
            assert bool(np.asarray(ctl.delay)[s]) == (
                ("q_fwd", s, "delay") in ev and ("u_fwd", s, "delay") in ev)


# --- integrity primitives (single device) -----------------------------------


def test_checksum_header_detects_flips():
    import jax
    import jax.numpy as jnp
    from repro.comm.faults import (checksum_header, flip_bits,
                                   payload_checksum, verify_header)
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (4, 16, 8))
    hdr = checksum_header(x, 7)
    assert bool(verify_header(x, hdr, 7))
    assert not bool(verify_header(x, hdr, 6))      # stale/reordered slab
    # inactive flip is a BIT-EXACT identity (clean ticks share the program)
    same = flip_bits(x, key, 3, 0)
    np.testing.assert_array_equal(np.asarray(same), np.asarray(x))
    # every active single-bit flip changes the checksum (exact word sum)
    for i in range(8):
        bad = flip_bits(x, jax.random.fold_in(key, i), 1, 1)
        assert not np.array_equal(np.asarray(bad), np.asarray(x))
        assert not bool(verify_header(bad, hdr, 7)), i
        assert int(payload_checksum(bad)) != int(payload_checksum(x)), i


def test_checksum_covers_packed_gather_payload():
    """The psum seam: the same header primitives protect the packed uint8
    containers the gather all-reduce ships (sub-byte codes included)."""
    import jax
    import jax.numpy as jnp
    from repro.comm.codecs import GridCodec
    from repro.comm.faults import (checksum_header, flip_bits, flip_payload,
                                   verify_header)
    from repro.core.quantize import uniform_grid
    codec = GridCodec(uniform_grid(4, -3.0, 3.0))
    key = jax.random.PRNGKey(3)
    payload = codec.encode(jax.random.normal(key, (32, 8)))
    packed = jax.tree.leaves(payload)
    assert any(leaf.dtype == jnp.uint8 for leaf in packed), [
        leaf.dtype for leaf in packed]
    hdr = checksum_header(payload, 0)
    assert bool(verify_header(payload, hdr, 0))
    for i in range(8):
        bad = flip_payload(payload, jax.random.fold_in(key, i), 1, 1)
        assert not bool(verify_header(bad, hdr, 0)), i
    # flip_payload corrupts the CODE BODY only: scale/zero headers intact
    bad = flip_payload(payload, key, 4, 1)
    dec = codec.decode(bad, shape=(32, 8), dtype=jnp.float32)
    assert np.isfinite(np.asarray(dec)).all()


# --- checkpoint hygiene + controller recovery hooks -------------------------


def test_ckpt_sweeps_stale_tmp_dirs(tmp_path):
    """Regression (satellite): a crash mid-save leaves `.tmp_*` staging
    litter; the next CheckpointManager construction sweeps it, keeping only
    committed checkpoints."""
    import jax.numpy as jnp
    from repro.ckpt.manager import CheckpointManager
    mgr = CheckpointManager(tmp_path, keep=3)
    tree = {"w": jnp.arange(4.0)}
    mgr.save(1, tree)
    mgr.save(2, tree)
    # a torn save: staging dir (and a stray staging file) with no commit
    litter_dir = tmp_path / ".tmp_abc123"
    litter_dir.mkdir()
    (litter_dir / "leaf_000000.npy").write_bytes(b"torn")
    (tmp_path / ".tmp_stray").write_text("x")
    mgr2 = CheckpointManager(tmp_path, keep=3)
    assert not list(tmp_path.glob(".tmp_*"))
    assert mgr2.all_steps() == [1, 2]          # committed ckpts untouched
    _, manifest = mgr2.restore(tree)
    assert manifest["step"] == 2


def test_controller_force_widest_cooldown_and_state_roundtrip():
    import json as _json

    from repro.comm.controller import BitWidthController, ControllerConfig
    mk = lambda: BitWidthController([1024, 2048], ControllerConfig(
        allowed_bits=(4, 8, 16), min_bits=4, max_bits=16, min_dwell=1,
        hysteresis=0.0, thresholds=((0.5, 4), (0.1, 8))))
    ctl = mk()
    assert ctl.assign([1.0, 1.0], 0) == (4, 4)     # residuals at peak
    ctl.force_widest(1, cooldown=3)
    for it in (1, 2, 3):                           # cooldown window
        assert ctl.assign([1.0, 1.0], it) == (16, 16), it
    # window closed: the untouched floor policy resumes where it would be
    assert ctl.assign([1.0, 1.0], 4) == (4, 4)
    # checkpointed control state round-trips through JSON and a fresh
    # instance continues the cooldown of the saved one
    ctl.force_widest(5, cooldown=4)
    sd = _json.loads(_json.dumps(ctl.state_dict()))
    ctl2 = mk()
    ctl2.load_state_dict(sd)
    assert ctl2.assign([1.0, 1.0], 6) == (16, 16)
    assert ctl2.assign([1.0, 1.0], 9) == (4, 4)
    assert ctl2.state_dict()["spent_bytes"] > 0


def test_train_adaptive_rollback_matches_clean_run(tmp_path):
    """Single-host recovery: a NaN poisoned into the state mid-run rolls
    back to the last checkpoint and the completed run's objectives EQUAL the
    clean run's (the rollback replays the poisoned iteration exactly)."""
    import jax
    import jax.numpy as jnp
    from repro.comm.controller import (BitWidthController, ControllerConfig,
                                       admm_edges, train_adaptive)
    from repro.comm.ledger import CommLedger
    from repro.core import pdadmm
    from repro.core.pdadmm import ADMMConfig
    key = jax.random.PRNGKey(0)
    V, d, C = 48, 12, 3
    X = jax.random.normal(key, (V, d))
    labels = jax.random.randint(jax.random.PRNGKey(1), (V,), 0, C)
    masks = {"train": jnp.ones((V,)), "val": jnp.ones((V,)),
             "test": jnp.ones((V,))}
    dims = [d, 8, 8, C]
    cfg = ADMMConfig(nu=1e-2, rho=1.0, fista_iters=3)
    grids = {b: pdadmm.calibrate_grid(key, X, dims, b) for b in (4, 8)}
    mk_ctl = lambda: BitWidthController(
        admm_edges(dims, V)[:len(dims) - 2],
        ControllerConfig(allowed_bits=(4, 8), min_bits=4, max_bits=8))
    _, clean = train_adaptive(key, X, labels, masks, dims, cfg, 8,
                              controller=mk_ctl(), ledger=CommLedger(),
                              grids_by_bits=grids)
    poisoned = {"n": 0}

    def hook(e, state):
        if e == 5 and poisoned["n"] == 0:
            poisoned["n"] += 1
            W = list(state.W)
            W[0] = W[0].at[0, 0].set(jnp.nan)
            return state._replace(W=W)
        return state

    led = CommLedger()
    _, hist = train_adaptive(key, X, labels, masks, dims, cfg, 8,
                             controller=mk_ctl(), ledger=led,
                             grids_by_bits=grids, ckpt=str(tmp_path),
                             ckpt_every=2, fault_hook=hook)
    assert poisoned["n"] == 1
    assert led.fault_counts()["step"]["rolled_back"] == 1
    assert hist["objective"] == clean["objective"]
    # resume from the same directory continues past the saved step
    _, hist2 = train_adaptive(key, X, labels, masks, dims, cfg, 12,
                              controller=mk_ctl(), ledger=CommLedger(),
                              grids_by_bits=grids, ckpt=str(tmp_path),
                              ckpt_every=4, resume=True)
    assert len(hist2["objective"]) < 12          # it resumed, not restarted
    assert np.isfinite(hist2["objective"]).all()


# --- distributed: no-fault identity + exact detection (subprocess) ----------


def test_sentinel_no_fault_bitwise_identity():
    """health=True (and a zero-rate FaultPlan) must change NOTHING about
    the math: state and metrics bitwise-equal to the plain step, in both
    exchange orderings, and the trained run's ledger keeps identical
    LOGICAL accounting — the +8 B integrity headers are physical-only."""
    out = _run(PRELUDE + """
from repro.comm.faults import SENTINEL_HEADER_BYTES
state = SP.init_stack(key, Xp, L, cfg)
step0, _ = SP.make_distributed_step(mesh, L, C, cfg)
s0, m0 = step0(state, Xp, labels, masks["train"])
good = SP.make_sentinel_primer(mesh)(state.q, state.u, state.p)
for tag, kw in (("health", dict(health=True)),
                ("zero-rate", dict(health=True, faults=F.FaultPlan(seed=7)))):
    steph, _ = SP.make_distributed_step(mesh, L, C, cfg, **kw)
    ctl = F.null_controls(n_stages) if tag == "health" else \\
        kw["faults"].controls(0, n_stages)
    (s1, _), m1 = steph((state, good), Xp, labels, masks["train"], ctl)
    for a, b in zip(jax.tree.leaves(s0), jax.tree.leaves(s1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=tag)
    assert float(m0["objective"]) == float(m1["objective"]), tag
    hlt = jax.device_get(m1["health"])
    assert [int(x) for x in hlt["wire_bad"]] == [0, 0, 0], (tag, hlt)
    assert not bool(hlt["objective_spike"]), tag
    # overlap ordering too
    stepo, _ = SP.make_distributed_step(mesh, L, C, cfg, overlap=True, **kw)
    fly = SP.make_overlap_primer(mesh, sentinel=True)(
        state.q, state.u, jnp.asarray(-1, jnp.int32))
    ((s2, _), _), m2 = stepo(((state, good), fly), Xp, labels,
                             masks["train"], ctl)
    for a, b in zip(jax.tree.leaves(s0), jax.tree.leaves(s2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=tag + "/overlap")
    print(tag, "IDENTITY_OK")

# trained-run view: objectives equal, ONE compiled step, logical ledger
# identical; wire bytes grow by exactly the headers (3 edges x links x 8 B
# per tick)
led_p, led_h = CommLedger(), CommLedger()
_, h_p = SP.distributed_train(mesh, key, Xp, labels, masks, L, C, cfg, 6,
                              ledger=led_p)
_, h_h = SP.distributed_train(mesh, key, Xp, labels, masks, L, C, cfg, 6,
                              ledger=led_h, health=True)
assert h_h["objective"] == h_p["objective"]
assert h_h["residual"] == h_p["residual"]
assert h_h["n_compiled_steps"] == 1, h_h["n_compiled_steps"]
assert h_h["faults"]["injected"] == 0 and h_h["faults"]["detected"] == 0
assert led_h.per_edge() == led_p.per_edge()        # logical bytes untouched
links = n_stages * dp_total
hdr = 6 * 3 * links * SENTINEL_HEADER_BYTES
assert led_h.total_wire_bytes() == led_p.total_wire_bytes() + hdr, (
    led_h.total_wire_bytes(), led_p.total_wire_bytes(), hdr)
print("NOFAULT_IDENTITY_OK")
""")
    assert "NOFAULT_IDENTITY_OK" in out


def test_wire_fault_detection_exact_accounting():
    """Every injected wire fault (flip/drop) fails EXACTLY one verdict per
    data-parallel ring — psummed wire_bad equals the host-side event
    enumeration times dp_total, edge by edge."""
    out = _run(PRELUDE + """
state = SP.init_stack(key, Xp, L, cfg)
good = SP.make_sentinel_primer(mesh)(state.q, state.u, state.p)
plan = F.FaultPlan(seed=7, flip_rate=0.5, drop_rate=0.2)
stepf, _ = SP.make_distributed_step(mesh, L, C, cfg, health=True,
                                    faults=plan)
hit = 0
for tick in range(6):
    ctl = plan.controls(tick, n_stages)
    (s, _), m = stepf((state, good), Xp, labels, masks["train"], ctl)
    det = [int(x) for x in jax.device_get(m["health"])["wire_bad"]]
    exp = {e: 0 for e in F.EDGES}
    for (e, s_, kind) in plan.events(tick, n_stages):
        if kind in ("drop", "flip"):
            exp[e] += dp_total
    assert det == [exp[e] for e in F.EDGES], (tick, det, exp)
    hit += sum(det)
    # substitution keeps the state finite whatever was corrupted
    assert all(bool(jax.device_get(m["health"])[k]) for k in
               ("p_finite", "W_finite", "b_finite", "z_finite"))
assert hit > 0, "plan at these rates must hit in 6 ticks"
print("DETECTION_EXACT_OK")
""")
    assert "DETECTION_EXACT_OK" in out


def test_chaos_determinism_and_accounting():
    """Same seed => identical injected trace AND bitwise-identical history,
    with every injected fault accounted: flips/drops are all detected and
    recovered in-step (x dp rings), still ONE compiled step."""
    out = _run(PRELUDE + """
plan = F.FaultPlan(seed=3, flip_rate=0.1, drop_rate=0.05, delay_rate=0.05,
                   blackouts=((1, 2, 2),))
runs = []
for overlap in (False, True):
    led1, led2 = CommLedger(), CommLedger()
    _, r1 = SP.distributed_train(mesh, key, Xp, labels, masks, L, C, cfg, 8,
                                 faults=plan, overlap=overlap, ledger=led1)
    _, r2 = SP.distributed_train(mesh, key, Xp, labels, masks, L, C, cfg, 8,
                                 faults=plan, overlap=overlap, ledger=led2)
    assert r1["faults"]["trace"] == r2["faults"]["trace"]
    np.testing.assert_array_equal(r1["objective"], r2["objective"])
    np.testing.assert_array_equal(r1["residual"], r2["residual"])
    assert r1["n_compiled_steps"] == 1, r1["n_compiled_steps"]
    f = r1["faults"]
    assert f["injected"] > 0
    # this plan has no sneaky faults: nothing escapes the header, so the
    # final-tick-unobserved delay tail is the only detected<injected slack
    assert f["detected"] == f["recovered"]
    assert 0 < f["detected"] <= f["injected"], f
    assert f["rolled_back"] == 0, f
    # the ledger's per-edge fault counters tell the same story as hist
    fc = led1.fault_counts()
    for total in ("injected", "detected", "recovered"):
        assert sum(v.get(total, 0) for v in fc.values()) == f[total], (
            total, fc, f)
    runs.append(r1)
# determinism holds ACROSS orderings at the trace level (same plan)
assert runs[0]["faults"]["trace"] == runs[1]["faults"]["trace"]
print("CHAOS_DET_OK")
""")
    assert "CHAOS_DET_OK" in out


def test_recovery_rollback_resume_elastic():
    """Acceptance: sneaky corruption (undetectable on the wire) trips the
    objective/finite sentinels, rolls back to the checkpoint, finishes
    within tolerance of the clean run; resume= continues from disk in a
    fresh call, and the SAME checkpoint restores onto a DIFFERENT mesh."""
    out = _run(PRELUDE + """
import shutil, tempfile
_, clean = SP.distributed_train(mesh, key, Xp, labels, masks, L, C, cfg, 10)
plan = F.FaultPlan(seed=11, sneaky_rate=0.08, flips_per_event=6)
d = tempfile.mkdtemp()
led = CommLedger()
_, hist = SP.distributed_train(mesh, key, Xp, labels, masks, L, C, cfg, 10,
                               faults=plan, ckpt=d, ckpt_every=2, ledger=led)
f = hist["faults"]
assert f["rolled_back"] >= 1, f          # sneaky MUST cost a rollback
assert f["injected"] > 0
assert len(hist["objective"]) == 10      # ...and the run still finishes
assert np.isfinite(hist["objective"]).all()
# within tolerance of the clean run (NOT bitwise: the rollback replays the
# tick against FRESH faults — transient-fault semantics — and surviving
# sneaky substitutions perturb the trajectory slightly)
assert abs(hist["objective"][-1] - clean["objective"][-1]) \\
    < 0.25 * clean["objective"][-1], (hist["objective"][-1],
                                      clean["objective"][-1])
assert hist["objective"][-1] < clean["objective"][0]   # it DID converge
assert led.fault_counts()["step"]["rolled_back"] == f["rolled_back"]
assert "faults" in led.summary()
# fresh call resumes from the checkpoint and extends the run
_, h2 = SP.distributed_train(mesh, key, Xp, labels, masks, L, C, cfg, 14,
                             ckpt=d, ckpt_every=2, resume=True)
assert 0 < len(h2["objective"]) < 14     # resumed mid-flight
assert np.isfinite(h2["objective"]).all()
assert h2["objective"][-1] <= h2["objective"][0]       # still descending
# ELASTIC: restore the same checkpoint onto a (1, 4) mesh
mesh2 = make_mesh((1, 4), ("data", "model"),
                  devices=jax.devices()[:4])
_, h3 = SP.distributed_train(mesh2, key, Xp, labels, masks, L, C, cfg, 14,
                             ckpt=d, ckpt_every=0, resume=True)
assert np.isfinite(h3["objective"]).all()
shutil.rmtree(d)
# the acceptance plan: seeded bit-flips + a stage blackout, interrupted
# at epoch 6 and resumed mid-chaos — the restored tick keeps the fault
# schedule aligned, every injection in the resumed window is accounted
# (x dp rings), and the finished run lands within tolerance of clean
plan2 = F.FaultPlan(seed=4, flip_rate=0.1, blackouts=((1, 4, 2),))
assert plan2.trace(10, n_stages), "plan must inject in 10 ticks"
d2 = tempfile.mkdtemp()
_, hA = SP.distributed_train(mesh, key, Xp, labels, masks, L, C, cfg, 6,
                             faults=plan2, ckpt=d2, ckpt_every=2)
led2 = CommLedger()
_, hB = SP.distributed_train(mesh, key, Xp, labels, masks, L, C, cfg, 10,
                             faults=plan2, ckpt=d2, ckpt_every=2,
                             resume=True, ledger=led2)
f = hB["faults"]
assert 0 < len(hB["objective"]) <= 4     # resumed at the saved epoch
assert f["trace"], "the resumed window must see some of the plan"
assert all(t >= 6 for (t, e, s, k) in f["trace"]), f["trace"]
assert f["injected"] == dp_total * len(f["trace"]), f
assert f["detected"] == f["recovered"]
assert f["rolled_back"] == 0, f          # all wire-detected, none sneaky
fc = led2.fault_counts()
assert sum(v.get("injected", 0) for v in fc.values()) == f["injected"]
assert abs(hB["objective"][-1] - clean["objective"][-1]) \\
    < 0.25 * clean["objective"][-1], (hB["objective"][-1],
                                      clean["objective"][-1])
shutil.rmtree(d2)
print("RECOVERY_OK")
""")
    assert "RECOVERY_OK" in out


def test_controller_rollback_forces_widest():
    """Controller + chaos: a rollback forces the widest legal width for the
    cooldown window (quantization noise out of the suspect set), with one
    compiled step per distinct width the schedule visits."""
    out = _run(PRELUDE + """
import shutil, tempfile
from repro.core import quantize
from repro.comm.controller import BitWidthController, ControllerConfig
plan = F.FaultPlan(seed=11, sneaky_rate=0.08, flips_per_event=6)
grids = {b: quantize.uniform_grid(b, -4.0, 4.0) for b in (3, 8)}
# thresholds pin the residual policy's floor to 3 bits for any nonzero
# residual ratio — the ONLY way this run can emit 8 is the force_widest
# cooldown a rollback triggers
ctl = BitWidthController([2 * V * h], ControllerConfig(
    allowed_bits=(3, 8), min_bits=3, max_bits=8, min_dwell=1,
    hysteresis=0.0, thresholds=((0.0, 3),)))
d = tempfile.mkdtemp()
_, hist = SP.distributed_train(mesh, key, Xp, labels, masks, L, C, cfg, 10,
                               faults=plan, ckpt=d, ckpt_every=2,
                               controller=ctl, grids_by_bits=grids)
assert hist["faults"]["rolled_back"] >= 1, hist["faults"]
assert hist["n_compiled_steps"] == len(set(hist["schedules"])), hist
# the post-rollback cooldown pins the schedule to the widest legal width
assert 8 in set(hist["schedules"]), hist["schedules"]
assert 3 in set(hist["schedules"]), hist["schedules"]
shutil.rmtree(d)
print("CTL_WIDEST_OK")
""")
    assert "CTL_WIDEST_OK" in out


@pytest.mark.slow
def test_chaos_sweep_long():
    """Long chaos sweep (slow): seeds x fault mixes x orderings — every run
    finishes finite with its whole trace accounted, and re-running any
    configuration reproduces the history bit for bit."""
    out = _run(PRELUDE + """
import shutil, tempfile
mixes = [
    dict(flip_rate=0.15),
    dict(drop_rate=0.1, delay_rate=0.08),
    dict(flip_rate=0.08, drop_rate=0.05, delay_rate=0.05,
         sneaky_rate=0.04, blackouts=((0, 3, 2), (1, 6, 1))),
]
for seed in (1, 2):
    for mix in mixes:
        plan = F.FaultPlan(seed=seed, flips_per_event=6, **mix)
        for overlap in (False, True):
            # determinism needs identical starting DISK state too: a shared
            # directory would let run 2's rollback restore run 1's later
            # checkpoint
            runs = []
            for _ in range(2):
                d = tempfile.mkdtemp()
                runs.append(SP.distributed_train(
                    mesh, key, Xp, labels, masks, L, C, cfg, 10,
                    faults=plan, overlap=overlap, ckpt=d, ckpt_every=3)[1])
                shutil.rmtree(d)
            r1, r2 = runs
            f = r1["faults"]
            assert np.isfinite(r1["objective"]).all(), (seed, mix, overlap)
            assert r1["objective"] == r2["objective"], (seed, mix, overlap)
            assert r1["faults"]["trace"] == r2["faults"]["trace"]
            assert f["detected"] == f["recovered"]
            assert f["detected"] <= f["injected"], f
            assert r1["n_compiled_steps"] == 1
            print("sweep", seed, sorted(mix), "overlap", overlap, "ok:",
                  {k: f[k] for k in ("injected", "detected", "rolled_back")})
print("SWEEP_OK")
""")
    assert "SWEEP_OK" in out
