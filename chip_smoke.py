"""Chip smoke run: stage-parallel pdADMM-G at pubmed's full Table-II size.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the 4-stage mesh on four chips

One chip: ``distributed_train`` on a (1, 1) ``("data", "model")`` mesh,
L = 8 layers of width h = 1000 over all 19,717 nodes, for a few iterations,
once with the fp32 wire and once with the pdADMM-G-Q p-wire (the paper's
grid {-1, ..., 20}, uint8 codes). Each run is repeated with
``REPRO_KERNELS=ref`` in the same process, and the Pallas and jnp results
are compared. A last phase compiles the step window by hand, checks that the
Pallas kernels reached the compiled program, and times the window.

``--four-chips``: the same trainer on a (1, 4) mesh (two layers per stage,
q/u sent forward and p backward by ``ppermute``) against a (1, 1) mesh on
the first device, both wires, and nothing else.

The script refuses to run without a TPU and never falls back to the CPU.
Timings are a smoke run's, not a benchmark's. The last line of standard
output is one JSON object: ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.analysis.jaxpr_tools import count_primitive  # noqa: E402
from repro.configs.gamlp_paper import GAMLP  # noqa: E402
from repro.core.pdadmm import ADMMConfig  # noqa: E402
from repro.core.quantize import integer_grid  # noqa: E402
from repro.graph.datasets import synthetic  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.kernels.relu_zupdate import ROW_TILE  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.parallel import stage_parallel as SP  # noqa: E402

DATASET = "pubmed"
HIDDEN = 1000          # the paper's width
LAYERS = 8
K_HOPS = 4
ITERS = 8
SEED = 0

# Limits on the relative gap ||a - b|| / ||b|| of each state leaf, and on the
# largest |a - b| / |b| of the objective over the iterations. "z tail" is the
# last V mod ROW_TILE rows of z: relu_zupdate's masked edge block, which a
# whole-leaf norm would dilute (5 of 19,717 rows). Each limit sits about 10x
# above the gaps read on a v5e chip (PERF.md). Both sides run the same
# iteration map -- on the CPU at a small size, where f32 matmuls round to
# f32, every leaf agrees to 1e-5 or better, and (1, 4) vs (1, 1) to 1e-12 in
# float64 (tests/test_distributed.py) -- so the gaps are the chip's f32
# matmul rounding (no precision is set; at precision HIGHEST the b gap of
# (1, 4) vs (1, 1) falls from 8e-3 to 2e-6).
# b = mean(z - pW) and u = nu (q - relu z) are differences of near-equal
# terms; their small norms magnify that rounding, hence their wider limits.
# q's limit covers the int8 wire, where rounding flips a few q codes by one
# grid step. A fault in a kernel tile, an edge block, a boundary exchange or
# a layer mask moves a leaf by O(1).
REF_TOL = {"objective": 1e-5, "p": 1e-4, "W": 1e-3, "b": 1e-1, "z": 1e-3,
           "z tail": 1e-3, "q": 1e-3, "u": 1e-2}        # Pallas vs jnp ref
MESH_TOL = {"objective": 1e-5, "p": 1e-4, "W": 1e-4, "b": 5e-2, "z": 1e-4,
            "z tail": 1e-3, "q": 1e-3, "u": 1e-3}       # (1, 4) vs (1, 1)

# XLA compile time as JAX reports it; a persistent-cache hit shows up as a
# short compile. (Trace and lowering events nest, so they are not summed.)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_durations = collections.Counter()


def _on_duration(event, secs, **_):
    _durations[event] += secs


def compile_seconds() -> float:
    return _durations[COMPILE_EVENT]


def configs():
    """The two wires of one GA-MLP configuration (configs/gamlp_paper.py)."""
    base = dict(nu=GAMLP.nu, rho=GAMLP.rho, fista_iters=GAMLP.fista_iters)
    grid = integer_grid(min(GAMLP.quant_levels), max(GAMLP.quant_levels))
    return {
        "fp32": ADMMConfig(**base),
        "int8": ADMMConfig(**base, quantize_p=GAMLP.quantize_p,
                           quantize_q=GAMLP.quantize_q, grid=grid),
    }


def build_data():
    ds = synthetic(DATASET, seed=SEED, scale=1.0)
    X = ds.augmented(K_HOPS)
    P0 = jax.random.normal(jax.random.PRNGKey(SEED), (X.shape[1], HIDDEN)) \
        * jnp.sqrt(2.0 / X.shape[1])
    Xp = jnp.maximum(X @ P0, 0.0)
    return ds, jax.block_until_ready(Xp)


FAILURES = []


def check(ok: bool, msg: str):
    """Record a failed check; the run goes on so one call prints every
    number, and ``main`` exits non-zero at the end."""
    if not ok:
        FAILURES.append(msg)
        print(f"FAILED: {msg}")


def train(mesh, ds, Xp, cfg):
    """One ``distributed_train`` run -> dict with the objective and residual
    histories, the final state on the host, the state bytes each device
    held, and wall and compile seconds."""
    c0, t0 = compile_seconds(), time.perf_counter()
    state, hist = SP.distributed_train(
        mesh, jax.random.PRNGKey(SEED), Xp, ds.labels, ds.masks, LAYERS,
        ds.n_classes, cfg, epochs=ITERS)
    held = collections.Counter()
    for leaf in state:
        for shard in leaf.addressable_shards:
            held[shard.device.id] += shard.data.nbytes
    state = jax.device_get(state)
    return dict(obj=hist["objective"], res=hist["residual"], state=state,
                held=dict(sorted(held.items())),
                wall=time.perf_counter() - t0, compile=compile_seconds() - c0)


def report(tag, run):
    """Print one run and check it: finite metrics and state, and an
    objective that falls over the window."""
    print(f"[{tag}] objective {fmt(run['obj'])}")
    print(f"[{tag}] residual  {fmt(run['res'])}")
    print(f"[{tag}] run {run['wall']:.3f} s incl. compile "
          f"{run['compile']:.3f} s; final state bytes per device id "
          f"{run['held']}")
    check(bool(np.all(np.isfinite(run["obj"] + run["res"]))),
          f"{tag}: non-finite metrics")
    for name, leaf in run["state"]._asdict().items():
        check(bool(np.all(np.isfinite(leaf))),
              f"{tag}: non-finite state leaf {name}")
    check(run["obj"][-1] < run["obj"][0], f"{tag}: objective did not fall")


def compare(tag, a, b, tol):
    """Relative gaps of run `a` against run `b`, each held to its limit in
    `tol`: the objective's largest |a-b|/|b| over the iterations, and
    ||a-b||/||b|| for each state leaf and for the tail rows of z."""
    oa, ob = np.asarray(a["obj"], np.float64), np.asarray(b["obj"], np.float64)
    gaps = {"objective": float(np.max(np.abs(oa - ob) / np.abs(ob)))}
    leaves = dict(zip(b["state"]._fields, zip(a["state"], b["state"])))
    tail = a["state"].z.shape[-2] % ROW_TILE
    leaves["z tail"] = (a["state"].z[..., -tail:, :],
                        b["state"].z[..., -tail:, :])
    for name, (x, y) in leaves.items():
        x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
        gaps[name] = float(np.linalg.norm(x - y) / np.linalg.norm(y))
    print(f"[{tag}] relative gaps (limit): " + ", ".join(
        f"{k} {v:.3e} ({tol[k]:g})" for k, v in gaps.items()))
    over = [k for k, v in gaps.items() if not v <= tol[k]]
    check(not over, f"{tag}: gap beyond its limit in {over}")


def peak_bytes(dev) -> int:
    return int((dev.memory_stats() or {}).get("peak_bytes_in_use", -1))


def fmt(xs):
    return "[" + ", ".join(f"{x:.6g}" for x in xs) + "]"


def one_chip(ds, Xp):
    dev = jax.devices()[0]
    mesh = make_mesh((1, 1), ("data", "model"), devices=[dev])
    final = None
    for wire, cfg in configs().items():
        run = train(mesh, ds, Xp, cfg)
        report(f"{wire} pallas", run)
        print(f"[{wire} pallas] peak_bytes_in_use {peak_bytes(dev)}")
        # the traced step must really dispatch the Pallas kernels
        step, _ = SP.make_distributed_step(mesh, LAYERS, ds.n_classes, cfg)
        shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), run["state"])
        jaxpr = jax.make_jaxpr(step)(shapes, Xp, ds.labels, ds.masks["train"])
        n_pallas = count_primitive(jaxpr.jaxpr, "pallas_call")
        print(f"[{wire} pallas] pallas_call count in the traced step: "
              f"{n_pallas}")
        check(n_pallas > 0, f"{wire}: no pallas_call in the traced step")

        policy = os.environ.get(ops.POLICY_ENV)
        os.environ[ops.POLICY_ENV] = "ref"
        try:
            ref = train(mesh, ds, Xp, cfg)
        finally:
            if policy is None:
                del os.environ[ops.POLICY_ENV]
            else:
                os.environ[ops.POLICY_ENV] = policy
        report(f"{wire} ref", ref)
        compare(f"{wire} pallas vs ref", run, ref, REF_TOL)
        if final is None:
            final = (cfg, run["state"])
    time_window(mesh, ds, Xp, *final)


def time_window(mesh, ds, Xp, cfg, state):
    """Compile the ITERS-step window by hand (the lax.scan that
    ``distributed_train`` runs), check the kernels reached the compiled
    program, and time it after one warm-up call."""
    step, specs = SP.make_distributed_step(mesh, LAYERS, ds.n_classes, cfg)
    put = lambda x, sp: jax.device_put(x, NamedSharding(mesh, sp))
    state = jax.tree.map(put, state, specs)
    dp = P("data")
    data = (put(Xp, dp), put(ds.labels, dp), put(ds.masks["train"], dp))
    window = jax.jit(lambda c, d: jax.lax.scan(
        lambda c, _: step(c, *d), c, None, length=ITERS))
    t0 = time.perf_counter()
    compiled = window.lower(state, data).compile()
    t_compile = time.perf_counter() - t0
    n_custom = compiled.as_text().count("tpu_custom_call")
    mem = compiled.memory_analysis()
    print(f"[window] compile {t_compile:.3f} s; tpu_custom_call in the "
          f"compiled HLO: {n_custom}; program bytes: arguments "
          f"{mem.argument_size_in_bytes}, outputs {mem.output_size_in_bytes}, "
          f"temporaries {mem.temp_size_in_bytes}")
    check(n_custom > 0, "no tpu_custom_call in the compiled window")
    state, _ = jax.block_until_ready(compiled(state, data))      # warm-up
    t0 = time.perf_counter()
    state, ms = jax.block_until_ready(compiled(state, data))
    dt = time.perf_counter() - t0
    check(bool(np.all(np.isfinite(np.asarray(ms["objective"])))),
          "non-finite objective in the timed window")
    print(f"[window] smoke timing, not a benchmark: {dt / ITERS:.6f} s/iter "
          f"over {ITERS} iterations after warm-up")
    print(f"[window] peak_bytes_in_use {peak_bytes(jax.devices()[0])}")


def four_chips(ds, Xp):
    devs = jax.devices()
    stage_mesh = make_mesh((1, 4), ("data", "model"), devices=devs[:4])
    one_mesh = make_mesh((1, 1), ("data", "model"), devices=devs[:1])
    for wire, cfg in configs().items():
        staged = train(stage_mesh, ds, Xp, cfg)
        report(f"{wire} (1,4)", staged)
        print(f"[{wire} (1,4)] peak_bytes_in_use per device "
              f"{[peak_bytes(d) for d in devs[:4]]}")
        check(len(staged["held"]) == 4 and min(staged["held"].values()) > 0,
              f"{wire}: the (1,4) state does not span four devices")
        single = train(one_mesh, ds, Xp, cfg)
        report(f"{wire} (1,1)", single)
        compare(f"{wire} (1,4) vs (1,1)", staged, single, MESH_TOL)
    print(f"peak_bytes_in_use per device after all runs "
          f"{[peak_bytes(d) for d in devs[:4]]}")


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--four-chips", action="store_true",
                        help="run the (1, 4) stage mesh against (1, 1)")
    args = parser.parse_args(argv)
    cache = enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)

    devs = jax.devices()
    dev = devs[0]
    print(f"platform {dev.platform}; device_kind {dev.device_kind}; "
          f"device count {len(devs)}; compile cache {cache}")
    if dev.platform != "tpu":
        print(f"no TPU (platform {dev.platform!r}); refusing to run",
              file=sys.stderr)
        return 1
    if not (ops.kernels_enabled()
            and ops.dispatch_policy() in ("auto", "pallas")):
        print(f"kernel dispatch policy {ops.dispatch_policy()!r} does not "
              "route to the Pallas kernels", file=sys.stderr)
        return 1
    if args.four_chips and len(devs) < 4:
        print(f"--four-chips needs 4 devices, found {len(devs)}",
              file=sys.stderr)
        return 1

    t0 = time.perf_counter()
    ds, Xp = build_data()
    print(f"data: {DATASET} V={Xp.shape[0]} K={K_HOPS} h={HIDDEN} "
          f"L={LAYERS} classes={ds.n_classes}; set-up "
          f"{time.perf_counter() - t0:.3f} s")
    (four_chips if args.four_chips else one_chip)(ds, Xp)
    print(f"compile seconds, whole run: {compile_seconds():.3f}")
    if FAILURES:
        print(f"{len(FAILURES)} check(s) failed: {FAILURES}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
