"""CPU tests of the chip benchmark's harness: the manifest and the files it
names, the input generator, the trace reduction, the counts and the metric
readers. Nothing here describes a TPU topology or needs a chip."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import counts  # noqa: E402
import run  # noqa: E402
import trace_reduce as tr  # noqa: E402

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
MODEL_API = ("inputs", "program", "reference", "leaves", "reference_leaves",
             "faults", "contraction_flops")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


# -- the manifest and the files it names --------------------------------------

def test_manifest_keys_names_and_units():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    entry_keys = {
        "configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
    }
    for key, keys in entry_keys.items():
        names = [e["name"] for e in MANIFEST[key]]
        assert len(names) == len(set(names)), key
        for e in MANIFEST[key]:
            assert set(e) - {"workloads"} == keys, (key, e["name"])
            assert NAME.match(e["name"]), e["name"]
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
    for w in MANIFEST["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for c in MANIFEST["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
    for m in MANIFEST["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {m["name"]: m["layer"] for m in MANIFEST["per_layer"]}
    e2e = {m["name"] for m in MANIFEST["end_to_end"]}
    assert all(m["moves"] in e2e for m in MANIFEST["per_layer"])
    assert all("\n" not in v and "\t" not in v for v in layers.values())
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_command_and_paths_stay_inside_the_benchmark():
    assert MANIFEST["paths"] == ["benchmarks/chip"]
    for word in MANIFEST["command"]:
        assert not word.startswith("/") and ".." not in word
    script = MANIFEST["command"][1]
    assert script.startswith("benchmarks/chip/")
    assert (ROOT / script).is_file()
    for c in MANIFEST["configs"]:
        assert c["file"].startswith("benchmarks/chip/")
    for path in (HERE).rglob("*"):
        if "__pycache__" in path.parts or path.is_dir():
            continue
        rel = path.relative_to(ROOT).as_posix()
        assert re.match(r"^[A-Za-z0-9_./-]+$", rel), rel


@pytest.mark.parametrize("workload",
                         [w["name"] for w in MANIFEST["workloads"]])
def test_every_cell_finds_its_files_by_name(workload):
    cell = run.Cell(workload)
    assert cell.config["name"] == cell.entry["config"]
    assert set(cell.config["reduced"]) == set(
        {c["name"]: c for c in MANIFEST["configs"]}[cell.entry["config"]]
        ["reduced"])
    assert cell.chips == cell.config["mesh"][0] * cell.config["mesh"][1]
    assert set(cell.limits) <= {"logits", "risk", "residual", "move", "p",
                                "W", "b", "z", "z_tail", "q", "u"}
    assert {"setup_s"} < set(cell.end_to_end)
    assert cell.per_layer
    for metric in cell.per_layer:
        assert callable(cell.metric_reader(metric).read)
    for name in MODEL_API:
        assert callable(getattr(cell.model, name)), name
    assert cell.ref is not None


def test_a_new_mix_and_metric_need_no_edit_of_an_existing_file(tmp_path):
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes()
              for p in (tmp_path / "benchmarks").rglob("*") if p.is_file()}
    d = tmp_path / "benchmarks" / "chip"
    mix = json.loads((d / "traffic" / "fp32.json").read_text())
    (d / "traffic" / "fp32_short.json").write_text(
        json.dumps(dict(mix, chunk=8)))
    (d / "limits" / "pubmed-L8.fp32_short.json").write_text(
        (d / "limits" / "pubmed-L8.fp32.json").read_text())
    (d / "metrics" / "window_s_probe.py").write_text(
        "def read(ctx):\n    return ctx.trace.window_s\n")
    manifest = json.loads(json.dumps(MANIFEST))
    manifest["workloads"].append(
        {"name": "pubmed-L8.fp32_short", "config": "pubmed-L8",
         "traffic": "fp32_short", "chips": 1, "why": "a test cell"})
    manifest["per_layer"].append(
        {"name": "window_s_probe", "unit": "s", "better": "lower",
         "source": "device_trace", "layer": "device (TPU v5e)",
         "moves": "iter_s", "workloads": ["pubmed-L8.fp32_short"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))

    cell = run.Cell("pubmed-L8.fp32_short", root=tmp_path)
    assert cell.traffic["chunk"] == 8
    assert "window_s_probe" in cell.per_layer
    trace = tr.Trace({0: []}, [], (0.0, 2e9))
    assert cell.metric_reader("window_s_probe").read(
        type("Ctx", (), {"trace": trace})) == 2.0
    old = run.Cell("pubmed-L8.fp32", root=tmp_path)
    assert "window_s_probe" not in old.per_layer
    for p, data in before.items():
        assert p.read_bytes() == data, p


@pytest.mark.parametrize("fault", [None, "unchanged"])
def test_a_second_model_needs_no_edit_of_an_existing_file(
        tmp_path, monkeypatch, fault):
    """A GA-MLP whose first layer is K·d -> h on the unprojected [V, K·d]
    augmentation, on the serial program, whose state has no q or u in its
    last layer: added as a model module, a reference, a configuration,
    limits and manifest entries, it runs through ``run.run_cell`` to
    ``correct``; with its step returning the state unchanged, not."""
    import jax

    from repro.core import pdadmm
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes()
              for p in (tmp_path / "benchmarks").rglob("*") if p.is_file()}
    d = tmp_path / "benchmarks" / "chip"
    added = HERE / "test_second_model"
    for src in added.rglob("*"):
        if src.is_dir() or "__pycache__" in src.parts:
            continue
        dst = d / src.relative_to(added)
        assert not dst.exists(), dst
        shutil.copy(src, dst)
    manifest = json.loads(json.dumps(MANIFEST))
    manifest["configs"].append(
        {"name": "gamlp_serial-L3",
         "source": "https://arxiv.org/abs/2105.09837",
         "file": "benchmarks/chip/configs/gamlp_serial-L3.json",
         "reduced": [], "why": "a test configuration"})
    manifest["workloads"].append(
        {"name": "gamlp_serial-L3.fp32", "config": "gamlp_serial-L3",
         "traffic": "fp32", "chips": 1, "why": "a test cell"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    if fault == "unchanged":
        step = pdadmm.iterate
        monkeypatch.setattr(pdadmm, "iterate",
                            lambda s, *a, **k: (s, step(s, *a, **k)[1]))

    cell = run.Cell("gamlp_serial-L3.fp32", root=tmp_path)
    K_d = cell.config["k_hops"] * cell.config["features"]
    result = run.run_cell(cell, 2 ** 33 + 29, 0.01, False,
                          jax.devices()[:1], log=lambda s: None)
    assert result["correct"] is (fault is None), result["compared"]
    assert set(result["compared"]) == set(cell.limits)
    data = cell.model.inputs(cell.config, 3)
    assert data.Psi.shape == (cell.config["nodes"], K_d)
    layers = cell.model.leaves(cell.model.program(
        cell, jax.devices()[:1], data)[2](3))
    assert layers[0]["W"].shape == (K_d, cell.config["hidden"])
    assert "p" not in layers[0] and "q" not in layers[-1]
    assert cell.model.contraction_flops(cell.config) == 2 * 300 * (
        3 * 160 * 128 + 5 * 128 * 128 + 5 * 128 * 3)
    for p, data in before.items():
        assert p.read_bytes() == data, p


def _run_entry(cwd, env_extra):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "pubmed-L8.fp32", "--seed", "2147483901", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_entry_refuses_the_cpu_before_building_inputs():
    proc = _run_entry(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert "platform cpu" in proc.stdout
    assert "inputs:" not in proc.stdout
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


def test_entry_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_entry(tmp_path, {"JAX_PLATFORMS": "cpu",
                                 "PYTHONPATH": ""})
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


# -- inputs -------------------------------------------------------------------

def test_per_hop_projection_equals_augment_then_project():
    import jax
    import jax.numpy as jnp

    import inputs
    V, E, C, D, K, h = 60, 240, 3, 20, 4, 16
    key = jax.random.PRNGKey(3)
    labels = jax.random.randint(key, (V,), 0, C)
    g = inputs.make_graph(jax.random.PRNGKey(4), labels, V, E, C)
    H = inputs.make_features(jax.random.PRNGKey(5), labels, C, D)
    P0 = jax.random.normal(jax.random.PRNGKey(6), (K * D, h))
    got = np.asarray(inputs.project_hops(g, H, P0, K), np.float64)

    A = np.zeros((V, V))
    np.add.at(A, (np.asarray(g.src), np.asarray(g.dst)),
              np.asarray(g.weight, np.float64))
    assert np.allclose(A, A.T)
    Hn = np.asarray(H, np.float64)
    blocks, cur = [], Hn
    for _ in range(K):
        blocks.append(cur)
        cur = A @ cur
    pre = np.concatenate(blocks, axis=1) @ np.asarray(P0, np.float64)
    want = np.maximum(pre, 0.0)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    # the renormalised adjacency: self loops, rows of D^-1/2 (A+I) D^-1/2
    assert np.all(np.diag(A) > 0)
    assert np.count_nonzero(np.asarray(H)) == V * min(inputs.SIGNAL_COLS, D)
    np.testing.assert_allclose(np.abs(Hn).sum(1), 1.0, rtol=1e-5)
    # same seed, same inputs; a seed wider than 32 bits keeps its high bits
    cfg = dict(nodes=V, edges=E, classes=C, features=D, train_nodes=9,
               k_hops=K, hidden=h)
    a, b = inputs.inputs_for(cfg, 2 ** 31 + 5), inputs.inputs_for(
        cfg, 2 ** 31 + 5)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    c = inputs.inputs_for(cfg, 2 ** 32 + 2 ** 31 + 5)
    assert not np.array_equal(a.Xp, c.Xp)
    assert float(a.train_mask.sum()) == 9
    assert jnp.all(a.Xp >= 0)


# -- the comparison's arithmetic ---------------------------------------------

LEAVES = ("p", "W", "b", "z", "q", "u")


def _layers(rng, n_layers, scale=1.0):
    return [{k: scale * rng.standard_normal(
        (4,) if k == "b" else (6, 4)).astype(np.float32)
        for k in LEAVES} for _ in range(n_layers)]


@pytest.mark.parametrize("case,want", [
    ("exact", 0.0), ("frozen", 1.0), ("one_leaf_half_again", 0.5),
    ("still_leaf_moved", 0.0)])
def test_move_is_the_worst_leafs_gap_of_change_norms(case, want):
    import compare
    rng = np.random.default_rng(0)
    start = _layers(rng, 3)
    step = _layers(rng, 3)
    for k in LEAVES:              # every leaf's change has norm 1 ...
        total = np.sqrt(sum(np.sum(d[k] ** 2) for d in step))
        for d in step:
            d[k] = d[k] / total
    for d in step:                # ... but q, which the reference holds
        d["q"] = np.zeros_like(d["q"])
    end = [{k: a[k] + d[k] for k in LEAVES} for a, d in zip(start, step)]
    mine = [dict(x) for x in end]
    if case == "frozen":
        mine = start
    elif case == "one_leaf_half_again":
        for a, d, m in zip(start, step, mine):
            m["z"] = a["z"] + 1.5 * d["z"]
    elif case == "still_leaf_moved":
        mine[2]["q"] = start[2]["q"] + step[2]["p"]
    got = compare.moves(mine, start, end, start)
    assert got["move"] == pytest.approx(want, abs=1e-6)
    if case == "one_leaf_half_again":
        assert got["move.z"] == pytest.approx(0.5, abs=1e-6)
        assert got["move.p"] == pytest.approx(0.0, abs=1e-6)


# -- trace reduction ----------------------------------------------------------

def _ops(*triples):
    return [tr.Op(n, float(s), float(e)) for n, s, e in triples]


def test_union_busy_and_exposed_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    ops = _ops(("fusion.1", 0, 10), ("collective-permute-done.2", 8, 20),
               ("fusion.3", 15, 18), ("copy.4", 30, 40))
    assert tr.busy_ns(ops) == 30
    permute = lambda o: o.name.startswith("collective-permute")
    # 8-20 in the permute, 8-10 and 15-18 hidden behind compute -> 7 exposed
    assert tr.exposed_ns(ops, permute) == 7
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.op_seconds(ops, permute) == pytest.approx(12e-9)


def test_op_events_are_parsed_from_their_hlo_text():
    assert tr.parse_hlo(
        "%copy.239 = f32[8,1000,1000]{2,1,0:T(8,128)} copy(f32[8,1000,1000]"
        "{2,1,0:T(8,128)} %state_W.1)") == ("copy.239", "copy")
    assert tr.parse_hlo(
        "%while.82 = (s32[]{:T(128)}, f32[8,19717,1000]{2,1,0:T(8,128)}) "
        "while((s32[]{:T(128)}, f32[8]{0}) %tuple.3), condition=%c") == (
            "while.82", "while")
    assert tr.parse_hlo("%collective-permute-done.1 = f32[1,5]{1,0} "
                        "collective-permute-done((f32[1,5]{1,0}) %s)") == (
        "collective-permute-done.1", "collective-permute-done")
    assert tr.parse_hlo("jit__scan_chunk(95)") == ("jit__scan_chunk(95)", "")


def test_idle_gaps_are_named_by_the_innermost_host_span():
    ops = _ops(("a", 0, 10), ("b", 20, 30), ("c", 50, 60))
    spans = _ops(("bench.chunk", 0, 35), ("bench.chunk", 45, 60))
    gaps = tr.idle_gaps(ops, (0, 60), spans)
    assert [(n, round(s * 1e9)) for n, s in gaps] == [
        ("bench.chunk", 10), ("between spans", 20)]
    assert tr.top([("x", 1.0), ("y", 3.0), ("x", 2.5)], 1) == [("x", 3.5)]
    clipped = tr.clip(_ops(("a", -5, 5), ("b", 70, 80)), (0, 60))
    assert clipped == _ops(("a", 0, 5))


# -- counts and peaks ---------------------------------------------------------

def test_contraction_flops_by_hand():
    # 5 contractions x 8 layers x 2 x 19,717 x 1000^2 = 1.57736 TFLOP
    assert counts.contraction_flops(19717, 1000, 8) == 1_577_360_000_000.0
    assert counts.contraction_flops(34493, 1000, 8) == 5 * 8 * 2 * 34493e6
    cell = run.Cell("pubmed-L8.fp32")
    assert cell.model.contraction_flops(cell.config) == 1_577_360_000_000.0


FISTA_LINE = (
    '  %_fista_zlast.3 = f32[157952,1024]{1,0:T(8,128)} custom-call(%pad.7, '
    '%pad.7, %pad.8, %reshape.4, %copy-done), custom_call_target='
    '"tpu_custom_call", operand_layout_constraints={f32[157952,1024]{1,0}, '
    'f32[157952,1024]{1,0}, f32[157952,1024]{1,0}, s32[157952,1]{1,0}, '
    'f32[157952,1]{1,0}}, metadata={op_name="jit(_fista_zlast)/pallas_call"}'
    ', backend_config={"custom_call_config":{"body":"BODY"}}')


def test_fista_call_bytes_from_its_padded_hlo_shapes():
    import base64
    body = base64.b64encode(
        b"\x00src/repro/kernels/fista_zlast.py\x00_fista_step_kernel\x00v\x00"
    ).decode()
    hlo = FISTA_LINE.replace("BODY", body) + "\n  %add.1 = f32[2] add()"
    calls = counts.pallas_calls(hlo)
    # three reads and one write of [157,952, 1024] f32, labels and mask
    want = 4 * 157952 * 1024 * 4 + 157952 * 4 + 157952 * 4
    assert calls == {"_fista_zlast.3": ("_fista_step_kernel", want)}
    assert counts.shape_bytes("(f32[2,3]{1,0}, u8[10]{0}, pred[])") == 35
    nested = base64.b64encode(b"\x00src/k/admm_pgrad.py\x00admm_pgrad.<locals>"
                              b".kernel\x00admm_pgrad.<locals>.kernel.<locals>"
                              b"._epilogue\x00").decode()
    calls = counts.pallas_calls(FISTA_LINE.replace("BODY", nested))
    assert calls["_fista_zlast.3"][0] == "admm_pgrad.<locals>.kernel"


MiB_BLOCK = 2048 * 128 * 4          # one [2048, 128] f32 array
PLACED_HLO = """\
HloModule jit__scan_chunk, is_scheduled=true
%body.1 (p: (s32[], f32[2048,128])) -> (s32[], f32[2048,128]) {
  %z_prev.1 = f32[2048,128]{1,0:T(8,128)} get-tuple-element(%p), index=1
  %copy-done.2 = f32[2048,128]{1,0:T(8,128)S(1)} copy-done(%copy-start.2)
  %reshape.3 = s32[2048,1]{1,0:T(8,128)S(1)} reshape(%labels.1)
  %reshape.4 = f32[2048,1]{1,0:T(8,128)} reshape(%mask.1)
  %_fista_zlast.4 = f32[2048,128]{1,0:T(8,128)} custom-call(%z_prev.1, %z_prev.1, %copy-done.2, %reshape.3, %reshape.4), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[2048,128]{1,0}, f32[2048,128]{1,0}, f32[2048,128]{1,0}, s32[2048,1]{1,0}, f32[2048,1]{1,0}}, backend_config={"custom_call_config":{"body":"BODY"}}
  ROOT %_fista_zlast.5 = f32[2048,128]{1,0:T(8,128)S(1)} custom-call(%z_prev.1, %_fista_zlast.4, %copy-done.2, %reshape.3, %reshape.4), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[2048,128]{1,0}, f32[2048,128]{1,0}, f32[2048,128]{1,0}, s32[2048,1]{1,0}, f32[2048,1]{1,0}}, backend_config={"custom_call_config":{"body":"BODY"}}
}
"""


def test_fista_bytes_leave_out_what_the_compiler_placed_in_vmem():
    """The compiler keeps some FISTA operands and results in VMEM (layout
    ``S(1)``) from one call to the next; they move no HBM bytes. A [V, 1]
    column in HBM stores 128 lanes a row (tile ``T(8,128)``). Counting
    every operand and result at its logical shape reads these two calls
    at 57% where they run at 50% of HBM bandwidth, and reads a program
    that keeps more in VMEM above 100%."""
    import base64
    body = base64.b64encode(
        b"\x00src/repro/kernels/fista_zlast.py\x00_fista_step_kernel\x00v\x00"
    ).decode()
    calls = counts.pallas_calls(PLACED_HLO.replace("BODY", body))
    # z_prev twice, the mask column and the result; then z_prev, the first
    # call's result and the mask column: a, the labels and the second
    # result lie in VMEM
    assert calls == {"_fista_zlast.4": ("_fista_step_kernel", 4 * MiB_BLOCK),
                     "_fista_zlast.5": ("_fista_step_kernel", 3 * MiB_BLOCK)}
    assert counts.hbm_bytes("f32[2048,1]{1,0:T(8,128)}") == MiB_BLOCK
    assert counts.hbm_bytes("f32[2048,128]{1,0:T(8,128)S(1)}") == 0
    assert counts.hbm_bytes("f32[19717,1000]{1,0:T(8,128)}") == (
        19720 * 1024 * 4)
    assert counts.hbm_bytes("f32[8,19717,1000]{2,1,0}") == 8 * 19717 * 4000

    at_half = lambda n: 2 * n * MiB_BLOCK / 819e9 * 1e9     # ns at 50%
    d0 = _ops(("_fista_zlast.4", 0, at_half(4)),
              ("_fista_zlast.5", at_half(4), at_half(7)))
    ctx = _ctx({0: d0}, calls)
    assert _read("roofline_pct.fista_zlast", ctx) == pytest.approx(50.0)


def test_fista_bytes_count_a_prefetch_into_vmem_once():
    """An async copy that brings ``a`` from HBM into VMEM before the solve
    reads its bytes from HBM once for all the calls that use it: they count
    for the first of them. With the labels and mask in VMEM and the z
    iterates kept there in turn, the calls would otherwise move next to
    nothing, and a solve whose every operand lay in VMEM would read as no
    share at all."""
    import base64
    body = base64.b64encode(
        b"\x00src/repro/kernels/fista_zlast.py\x00_fista_step_kernel\x00v\x00"
    ).decode()
    prefetch = PLACED_HLO.replace(
        "  %copy-done.2",
        "  %a.1 = f32[2048,128]{1,0:T(8,128)} get-tuple-element(%p), index=2\n"
        "  %copy-start.2 = (f32[2048,128]{1,0:T(8,128)S(1)}, "
        "f32[2048,128]{1,0:T(8,128)}, u32[]{:S(2)}) copy-start(%a.1)\n"
        "  %copy-done.2").replace("BODY", body)
    calls = counts.pallas_calls(prefetch)
    assert calls == {"_fista_zlast.4": ("_fista_step_kernel", 5 * MiB_BLOCK),
                     "_fista_zlast.5": ("_fista_step_kernel", 3 * MiB_BLOCK)}
    # a copy whose source already lies in VMEM reads nothing from HBM
    in_vmem = prefetch.replace("f32[2048,128]{1,0:T(8,128)}, u32[]",
                               "f32[2048,128]{1,0:T(8,128)S(1)}, u32[]")
    assert counts.pallas_calls(in_vmem)["_fista_zlast.4"][1] == 4 * MiB_BLOCK


@pytest.mark.parametrize("config", ["pubmed-L8", "coauthor_physics"])
def test_fista_call_bytes_are_what_its_grid_and_blocks_move(config,
                                                            monkeypatch):
    """One ``fista_step`` call at the padded rows of pubmed (19,717 ->
    19,968) and of coauthor_physics (34,493 -> 34,560), lowered for the
    TPU: ``pallas_calls`` counts what the kernel's grid and BlockSpecs move,
    V // bm steps of three [bm, N] reads, two [bm, 1] reads and one [bm, N]
    write. Lowered, nothing is yet placed in VMEM or tiled; in the compiled
    program each [bm, 1] block of a column that lies in HBM is stored, and
    moved, as [bm, 128] (tile T(8,128)), and a block in VMEM moves no HBM
    bytes, so the count of a compiled call differs from this one by those
    two terms alone."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from repro.kernels import fista_zlast, ops
    nodes = {"pubmed-L8": 19717, "coauthor_physics": 34493}[config]
    V, N = ops.padded_shape("fista_zlast", (nodes, 5))
    seen = []
    pallas_call = pl.pallas_call

    def record(kernel, **kw):
        seen.append(kw)
        return pallas_call(kernel, **kw)
    monkeypatch.setattr(pl, "pallas_call", record)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    step = lambda zp, zc, a, lab, m: fista_zlast.fista_step(
        zp, zc, a, lab, m, mom=0.5, step=0.99, nu=0.01, n_classes=5)
    text = jax.jit(step).trace(
        f32(V, N), f32(V, N), f32(V, N),
        jax.ShapeDtypeStruct((V, 1), jnp.int32), f32(V, 1)).lower(
        lowering_platforms=("tpu",)).as_text(dialect="hlo")
    (spec,) = seen
    (steps,) = spec["grid"]
    block = lambda b: 4 * b.block_shape[0] * b.block_shape[1]
    moved = steps * (sum(block(b) for b in spec["in_specs"])
                     + block(spec["out_specs"]))
    bm = spec["out_specs"].block_shape[0]
    assert (steps, N) == (V // bm, 128)
    assert moved == V // bm * (3 * bm * N + 2 * bm + bm * N) * 4
    ((_, counted),) = counts.pallas_calls(text).values()
    assert counted == moved
    # the two columns as a compiled program lays them out in HBM
    tiled = counts.hbm_bytes(f"s32[{V},1]{{1,0:T(8,128)}}")
    assert tiled == steps * bm * 128 * 4


def test_peaks_are_keyed_by_device_kind():
    v5e = counts.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        counts.peaks("TPU v99")


# -- metric readers on a hand-made trace --------------------------------------

def _ctx(devices, kernels, iterations=10, chips=1, program_bytes=0):
    import collections
    cell = run.Cell("pubmed-L8.fp32")
    trace = tr.Trace(devices, [], (0.0, 1e9))
    Ctx = collections.namedtuple(
        "Ctx", "trace hlo kernels program_bytes iterations peaks config "
        "chips model")
    return Ctx(trace, "", kernels, program_bytes, iterations,
               counts.peaks("TPU v5 lite"), cell.config, chips, cell.model)


def _read(metric, ctx):
    return run.Cell("pubmed-L8.fp32").metric_reader(metric).read(ctx)


def test_metric_readers_on_a_hand_made_trace():
    fista_bytes = 819e6          # 1 ms at 819 GB/s
    kernels = {"_fista_zlast.3": ("_fista_step_kernel", fista_bytes),
               "custom-call.9": ("_matmul_kernel", 1e6)}
    d0 = _ops(("_fista_zlast.3", 0, 2e6),            # 2 ms: 50% roofline
              ("custom-call.9", 2e6, 4e6),
              ("fusion.5", 4e6, 5e6),
              ("collective-permute-done.1", 5e6, 9e6),
              ("fusion.6", 8e6, 10e6))
    d1 = _ops(("fusion.7", 0, 1e6))
    ctx = _ctx({0: d0, 1: d1}, kernels, iterations=2,
               program_bytes=3 * 2 ** 29)
    assert _read("device_idle_pct", ctx) == pytest.approx(99.9)
    assert _read("roofline_pct.fista_zlast", ctx) == pytest.approx(50.0)
    # Pallas 4 ms of 11 ms busy
    assert _read("pallas_busy_pct", ctx) == pytest.approx(400 / 11)
    assert _read("hbm_gib", ctx) == 1.5
    flops = counts.contraction_flops(19717, 1000, 8) * 2
    assert _read("mfu_pct", ctx) == pytest.approx(100 * flops / 197e12)
    empty = _ctx({0: _ops(("fusion.1", 0, 1))}, {}, iterations=2)
    for m in ("roofline_pct.fista_zlast", "pallas_busy_pct", "hbm_gib"):
        assert _read(m, empty) is None
