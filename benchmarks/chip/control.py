"""Readings that the limits of ``limits/<cell>.json`` are set from.

    python3 benchmarks/chip/control.py --workload pubmed-L8.fp32 \\
        --seeds 11 12 13 [--faults]

For each seed, in one process and without a measured window:

* ``program``: the program's first chunk (the window's own program, driven
  as set-up drives it) against the reference: the lower readings;
* ``control``: the reference computed in bfloat16, put in the program's
  place: the upper readings;
* with ``--faults``, the reference put in the program's place with a fault
  planted: ``unchanged`` (the state never moves), ``half_batch`` (only the
  first half of the nodes is updated; b's mean is over that half),
  ``altered`` (the last layer's first logit of every node is raised by 1
  where it is produced).

One JSON line per seed goes to standard output: the readings, and under
``changes`` each reading's per-leaf norms of the change from its start
(``compare.change_norms``). Like ``run.py``, it refuses to run without a
TPU; tests drive ``readings`` on the CPU at a small size.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import run  # noqa: E402
from compare import change_norms, gaps, stacked  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402


def half_batch(ref):
    def iterate(layers, labels, mask, prm):
        h = layers[0].p.shape[0] // 2
        top = [ref.Layer(l.p[:h], l.W, l.b, l.z[:h], l.q[:h], l.u[:h])
               for l in layers]
        new, res = ref.iterate(top, labels[:h], mask[:h], prm)
        del top
        join = lambda a, b: jnp.concatenate([a, b[h:]])
        return [ref.Layer(join(n.p, o.p), n.W, n.b, join(n.z, o.z),
                          join(n.q, o.q), join(n.u, o.u))
                for n, o in zip(new, layers)], res
    return iterate


def altered(ref):
    def iterate(layers, labels, mask, prm):
        new, res = ref.iterate(layers, labels, mask, prm)
        last = new[-1]
        new[-1] = last._replace(z=last.z.at[:, 0].add(1.0))
        return new, res
    return iterate


def readings(cell, seed: int, devices, faults: bool, log=print) -> dict:
    prog = run.Program(cell, seed, devices, log)
    _, res, checked = run.warm_up(prog)
    prog.state = None
    start, inputs = jax.device_get(prog.start_state()), prog.inputs
    del prog
    gc.collect()
    t0 = time.perf_counter()
    ref_start, ref_layers, ref_res = run.reference_run(cell, seed, inputs)
    log(f"seed {seed}: reference {time.perf_counter() - t0:.3f} s")
    # the readings below each hold a whole state on the device; keep the
    # reference on the host meanwhile
    ref_start, ref_layers = jax.device_get((ref_start, ref_layers))
    against = (inputs.labels, inputs.train_mask, cell.config["classes"])
    out = {"seed": seed, "changes": {}}

    def record(name, end, end_res, begin):
        out[name] = gaps(end, ref_layers, end_res, ref_res, *against,
                         begin, ref_start)
        out["changes"][name] = change_norms(end, begin, ref_layers,
                                            ref_start)

    record("program", checked, res, start)
    del checked, start
    t0 = time.perf_counter()
    low0, low, low_res = run.reference_run(cell, seed, inputs,
                                           dtype=jnp.bfloat16)
    log(f"seed {seed}: control {time.perf_counter() - t0:.3f} s")
    record("control", stacked(low), low_res, stacked(low0))
    del low0, low
    if faults:
        still = stacked(ref_start)
        record("unchanged", still, [0.0] * len(ref_res), still)
        planted = {"half_batch": half_batch(cell.ref),
                   "altered": altered(cell.ref)}
        for name, iterate in planted.items():
            _, layers, fres = run.reference_run(cell, seed, inputs,
                                                iterate=iterate)
            record(name, stacked(layers), fres, still)
            del layers
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    parser.add_argument("--faults", action="store_true")
    args = parser.parse_args(argv)
    cell = run.Cell(args.workload)

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    print(f"platform {devices[0].platform}; device_kind "
          f"{devices[0].device_kind}; device count {len(devices)}",
          flush=True)
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("needs a TPU with the cell's chips; refusing to run",
              file=sys.stderr)
        return 1
    for seed in args.seeds:
        out = readings(cell, seed, devices, args.faults,
                       log=lambda s: print(s, flush=True))
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
