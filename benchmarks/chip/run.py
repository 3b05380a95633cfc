"""Chip benchmark of stage-parallel pdADMM-G: one cell, one run.

    python3 benchmarks/chip/run.py --workload pubmed-L8.fp32 --seed 7 \\
        --seconds 20 --trace 0

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a model
configuration (``configs/<config>.json``) and a job (``traffic/<mix>.json``).
The configuration names its model module, ``models/<config["model"]>.py``,
and its plain reference, ``reference/<config["reference"]>.py``. The
harness reaches everything that is particular to a model through that
module:

* ``inputs(config, seed)``: the cell's data, made on the device from the
  seed, with ``labels`` and ``train_mask`` among its fields;
* ``program(cell, devices, data)``: the program's step on its normal
  path, its arguments placed as the program places them, and
  ``start(seed)``, the seed's start state made by the program's own
  initialiser;
* ``reference(cell, seed, data, devices, dtype, iterate)``: the
  reference's start from the same data and its step,
  ``step(state) -> (state, primal residual)``, computed in ``dtype``;
  ``iterate`` replaces the reference's iteration (``control.py``'s faults);
* ``leaves(state)``, ``reference_leaves(state)``: the leaves the comparison
  reads, one mapping of leaf name to array per layer (a leaf that a layer
  does not have, or that is a fixed input, is left out of that layer's);
* ``faults(cell)``: the reference's iteration with faults planted, by name;
* ``contraction_flops(config)``: the contraction FLOPs of one iteration.

The run drives the step by successive ``run_chunked`` calls of one chunk
each:

* set-up: inputs, state, and one warm-up chunk, whose state is kept on
  the host for the check;
* window: chunk after chunk, carrying the state, until ``--seconds`` have
  passed;
* check: once the window has closed and the program's state is freed, the
  program's start state is made again from the seed, the reference repeats
  the warm-up chunk from the same seed, and ``compare.py`` holds every
  number to the cell's limit in ``limits/<cell>.json``.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` traces
the window and reports its per-layer metrics, each read by
``metrics/<name>.py``. The last line of standard output is one JSON object.
The run refuses to start without a TPU or with fewer chips than the cell
asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, Optional  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (HERE, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import trace_reduce as tr  # noqa: E402
from compare import gaps, judge  # noqa: E402
from counts import pallas_calls, peaks as peaks_of  # noqa: E402
from repro.core import pdadmm  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

SPAN = "bench.chunk"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load_json(path: Path):
    return json.loads(Path(path).read_text())


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + re.sub(r"\W", "_", path.stem), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """Everything a run needs to know about one cell, found by name: the
    manifest entry, the configuration, its model module and reference, the
    job, the limits of the check and the readers of its per-layer
    metrics."""

    def __init__(self, name: str, root: Path = ROOT):
        manifest = load_json(root / "BENCHMARK.json")
        cells = {w["name"]: w for w in manifest["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                           f"known: {sorted(cells)}")
        self.dir = root / HERE.relative_to(ROOT)
        self.name = name
        self.entry = cells[name]
        configs = {c["name"]: c for c in manifest["configs"]}
        self.config = load_json(root / configs[self.entry["config"]]["file"])
        self.traffic = load_json(self.dir / "traffic" /
                                 f"{self.entry['traffic']}.json")
        self.limits = load_json(self.dir / "limits" / f"{name}.json")
        self.chips = int(self.entry["chips"])
        self.end_to_end = {m["name"]: m for m in manifest["end_to_end"]
                           if name in m.get("workloads", [name])}
        self.per_layer = {m["name"]: m for m in manifest["per_layer"]
                          if name in m.get("workloads", [name])}

    @functools.cached_property
    def model(self):
        """The configuration's model module (loaded once)."""
        return load_module(self.dir / "models" / f"{self.config['model']}.py")

    @functools.cached_property
    def ref(self):
        """The configuration's plain reference module (loaded once, so its
        jitted functions compile once per process)."""
        return load_module(self.dir / "reference" /
                           f"{self.config['reference']}.py")

    def metric_reader(self, metric: str):
        return load_module(self.dir / "metrics" / f"{metric}.py")


class Program:
    """The system under test, as the window drives it."""

    def __init__(self, cell: Cell, seed: int, devices, log):
        self.chunk = int(cell.traffic["chunk"])
        t0 = time.perf_counter()
        self.inputs = jax.block_until_ready(
            cell.model.inputs(cell.config, seed))
        shapes = [tuple(x.shape) for x in jax.tree.leaves(self.inputs)]
        log(f"inputs: {cell.config['name']} ({cell.config['model']}) "
            f"{shapes}; {time.perf_counter() - t0:.3f} s")
        self.step, self.args, self._start = cell.model.program(
            cell, devices, self.inputs)
        self.seed = seed
        t0 = time.perf_counter()
        self.state = self.start_state()
        log(f"start state: {time.perf_counter() - t0:.3f} s")

    def start_state(self):
        """The seed's start state, as the program makes it."""
        return jax.block_until_ready(self._start(self.seed))

    def compile_chunk(self):
        """The chunk program, from the cache -> (bytes it needs on the
        fullest device, its memory analysis, its HLO text)."""
        compiled = pdadmm._scan_chunk.lower(
            self.state, self.args, step_fn=self.step,
            length=self.chunk).compile()
        mem = compiled.memory_analysis()
        need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
        return need, mem, compiled.as_text()

    def run_chunk(self):
        """One chunk through ``run_chunked``; its device_get of the
        metrics fences the chunk's end."""
        self.state, ms = pdadmm.run_chunked(self.step, self.state, self.args,
                                     self.chunk, chunk=self.chunk)
        return ms


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def warm_up(prog: Program):
    """The first chunk of the window's own program from the seed's state ->
    (metrics, residuals, the state on the host)."""
    ms = prog.run_chunk()
    return ms, [float(x) for x in ms["residual"]], jax.device_get(prog.state)


def reference_run(cell: Cell, seed: int, inputs, devices,
                  dtype=jnp.float32, iterate=None):
    """The reference over one chunk from the seed -> (start state, end
    state, residuals). ``iterate`` replaces the reference's iteration
    (faults)."""
    start, step = cell.model.reference(cell, seed, inputs, devices, dtype,
                                       iterate)
    state, res = start, []
    for _ in range(int(cell.traffic["chunk"])):
        state, r = step(state)
        res.append(float(r))
    return start, state, res


def reference_check(cell: Cell, seed: int, inputs, devices, checked,
                    program_start, program_res):
    """Run the reference over the warm-up chunk; the gaps of the program."""
    start, end, res = reference_run(cell, seed, inputs, devices)
    model = cell.model
    return gaps(model.leaves(checked), model.reference_leaves(end),
                program_res, res, inputs.labels, inputs.train_mask,
                int(cell.config["classes"]), model.leaves(program_start),
                model.reference_leaves(start))


def read_trace(cell: Cell, log_dir: str, hlo: str,
               kernels: Dict[str, tuple], program_bytes: int,
               iterations: int, peaks: dict, n_devices: int):
    t = tr.load(log_dir, SPAN)
    used = {d: ops for d, ops in sorted(t.devices.items())
            if d < n_devices and ops}
    t = tr.Trace(used, t.spans, t.window)
    ctx = collections.namedtuple(
        "Context", "trace hlo kernels program_bytes iterations peaks config "
        "chips model")(t, hlo, kernels, program_bytes, iterations, peaks,
                       cell.config, cell.chips, cell.model)
    values = {}
    for name, m in cell.per_layer.items():
        v = cell.metric_reader(name).read(ctx)
        if v is not None:
            values[name] = {"value": float(v), "unit": m["unit"]}
    label = lambda o: kernels[o.name][0] if o.name in kernels \
        else o.kind or re.sub(r"\.\d+$", "", o.name)
    ops = [o for d in used.values() for o in d]
    gaps_named = [g for d in used.values()
                  for g in tr.idle_gaps(d, t.window, t.spans)]
    breakdown = {
        "device_ops": [[k, v / max(len(used), 1)] for k, v in tr.top(
            (label(o), (o.end - o.start) * 1e-9) for o in ops)],
        "idle_gaps": [[k, v / max(len(used), 1)]
                      for k, v in tr.top(gaps_named)],
    }
    busy = [tr.busy_ns(d) * 1e-9 for d in used.values()]
    return values, breakdown, sum(busy) / max(len(busy), 1), t.window_s


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             log=print) -> dict:
    """One run of one cell on ``devices``; returns the result object."""
    compiles = collections.Counter()
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, secs, **_: compiles.update({ev: 1})
        if ev == COMPILE_EVENT else None)
    dev = devices[0]
    used = devices[:cell.chips]
    peaks = peaks_of(dev.device_kind) if dev.platform == "tpu" else {}

    prog = Program(cell, seed, devices, log)
    t0 = time.perf_counter()
    ms, warm_res, checked = warm_up(prog)
    log(f"warm-up chunk and state to the host: "
        f"{time.perf_counter() - t0:.3f} s")
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.3f} s; warm-up residual {warm_res[0]:.6g} -> "
        f"{warm_res[-1]:.6g}; objective {float(ms['objective'][0]):.6g} -> "
        f"{float(ms['objective'][-1]):.6g}")

    n_compiles = sum(compiles.values())
    iters = bad = 0
    tmp = tempfile.TemporaryDirectory() if trace else None
    if trace:
        jax.profiler.start_trace(tmp.name)
    t0 = time.perf_counter()
    while True:
        if trace:
            with jax.profiler.TraceAnnotation(SPAN):
                ms = prog.run_chunk()
        else:
            ms = prog.run_chunk()
        iters += prog.chunk
        bad += int(np.sum(~(np.isfinite(ms["residual"])
                            & np.isfinite(ms["objective"]))))
        wall = time.perf_counter() - t0
        if wall >= seconds:
            break
    if trace:
        jax.profiler.stop_trace()
    n_window_compiles = sum(compiles.values()) - n_compiles
    peak = peak_bytes(used)
    log(f"window: {iters} iterations in {wall:.3f} s; "
        f"{wall / iters:.6f} s/iteration; compiles in the window "
        f"{n_window_compiles}; peak_bytes_in_use {peak} (runtime counter, "
        f"fullest chip)")

    result = {"attempted": iters, "failed": bad}
    if trace:
        # the chunk program again, from the cache, for its memory analysis
        # and the names of its Pallas calls; after the window, out of set-up
        need, mem, hlo = prog.compile_chunk()
        kernels = pallas_calls(hlo)
        per_kernel = collections.Counter(k for k, _ in kernels.values())
        log(f"chunk program: arguments {mem.argument_size_in_bytes} outputs "
            f"{mem.output_size_in_bytes} temporaries {mem.temp_size_in_bytes}"
            f" aliased {mem.alias_size_in_bytes} -> {need} B on the fullest "
            f"device; {len(kernels)} Pallas calls "
            f"{sorted(per_kernel.items())}")
        values, breakdown, busy_s, window_s = read_trace(
            cell, tmp.name, hlo, kernels, need, iters, peaks, cell.chips)
        tmp.cleanup()
        result["metrics"] = values
        result["breakdown"] = breakdown
    else:
        e2e = {"iter_s": wall / iters, "setup_s": setup_s}
        result["metrics"] = {k: {"value": e2e[k], "unit": m["unit"]}
                             for k, m in cell.end_to_end.items()}
    result["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(devices), "memory_peak_bytes": peak}
    if trace:
        result["device"].update(busy_s=busy_s, window_s=window_s)

    t0 = time.perf_counter()
    prog.state = ms = None
    gc.collect()
    start, inputs = jax.device_get(prog.start_state()), prog.inputs
    del prog
    gc.collect()
    values = reference_check(cell, seed, inputs, used, checked, start,
                             warm_res)
    ok, lines = judge(values, cell.limits)
    log("readings not compared: " + ", ".join(
        f"{k} {v:.6e}" for k, v in values.items() if k not in cell.limits))
    log(f"reference check: {time.perf_counter() - t0:.3f} s")
    result["correct"] = bool(ok and bad == 0)
    result["compared"] = {k: {"value": values.get(k, math.nan),
                              "limit": lim}
                          for k, lim in cell.limits.items()}
    result["_lines"] = lines
    return result


def main(argv: Optional[list] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    cell = Cell(args.workload)

    cache = enable_compile_cache()
    # every program of the run goes to the cache, however fast it compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    dev = devices[0]
    print(f"platform {dev.platform}; device_kind {dev.device_kind}; "
          f"device count {len(devices)}; compile cache {cache}", flush=True)
    if dev.platform != "tpu":
        print(f"no TPU (platform {dev.platform!r}); refusing to run",
              file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"{cell.name} needs {cell.chips} chips, found {len(devices)}",
              file=sys.stderr)
        return 1

    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices, log=lambda s: print(s, flush=True))
    lines = result.pop("_lines")
    compared = result.pop("compared")
    result["compared"] = compared            # the last key of the line
    for line in lines:
        print(f"compared: {line}", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
