"""Readings that the limits of ``limits/<cell>.json`` are set from.

    python3 benchmarks/chip/control.py --workload pubmed-L8.fp32 \\
        --seeds 11 12 13 [--faults]

For each seed, in one process and without a measured window:

* ``program``: the program's first chunk (the window's own program, driven
  as set-up drives it) against the reference: the lower readings;
* ``control``: the reference computed in bfloat16, put in the program's
  place: the upper readings;
* with ``--faults``, the reference put in the program's place with a fault
  planted: ``unchanged`` (the state never moves), and each fault of the
  model module's ``faults`` (for ``gamlp_projected``: ``half_batch``, only
  the first half of the nodes updated, b's mean over that half; ``altered``,
  the last layer's first logit of every node raised by 1 where it is
  produced).

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
from compare import change_norms, gaps  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402


def readings(cell, seed: int, devices, faults: bool, log=print) -> dict:
    model = cell.model
    prog = run.Program(cell, seed, devices, log)
    _, res, checked = run.warm_up(prog)
    prog.state = None
    start, inputs = jax.device_get(prog.start_state()), prog.inputs
    del prog
    gc.collect()
    t0 = time.perf_counter()
    ref_start, ref_end, ref_res = run.reference_run(cell, seed, inputs,
                                                    devices)
    log(f"seed {seed}: reference {time.perf_counter() - t0:.3f} s")
    # the readings below each hold a whole state on the device; keep the
    # reference on the host meanwhile
    ref_start, ref_end = jax.device_get((model.reference_leaves(ref_start),
                                         model.reference_leaves(ref_end)))
    against = (inputs.labels, inputs.train_mask, cell.config["classes"])
    out = {"seed": seed, "changes": {}}

    def record(name, end, end_res, begin):
        out[name] = gaps(end, ref_end, end_res, ref_res, *against,
                         begin, ref_start)
        out["changes"][name] = change_norms(end, begin, ref_end, ref_start)

    record("program", model.leaves(checked), res, model.leaves(start))
    del checked, start
    t0 = time.perf_counter()
    low0, low, low_res = run.reference_run(cell, seed, inputs, devices,
                                           dtype=jnp.bfloat16)
    log(f"seed {seed}: control {time.perf_counter() - t0:.3f} s")
    record("control", model.reference_leaves(low), low_res,
           model.reference_leaves(low0))
    del low0, low
    if faults:
        record("unchanged", ref_start, [0.0] * len(ref_res), ref_start)
        for name, iterate in model.faults(cell).items():
            _, end, fres = run.reference_run(cell, seed, inputs, devices,
                                             iterate=iterate)
            record(name, model.reference_leaves(end), fres, ref_start)
            del end
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
