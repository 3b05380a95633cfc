"""Program-contract linter battery (repro.analysis.contracts + lint CLI).

Two halves, per the linter's own standard of proof:

* clean run — every registered step/psum configuration must pass every
  contract family with zero error findings under the CURRENT kernel
  policy (the suite runs on both ``REPRO_KERNELS`` legs in CI, so both
  dispatch plans get exercised);
* mutation battery — each contract family must actually BITE: for every
  family we mutate exactly one invariant through the engine's sanctioned
  hooks (``overrides`` re-kwargs the traced step while the plan keeps the
  spec's declared kwargs; ``wrap`` post-composes onto the step; ``pinned``
  / ``variants`` feed the cache family; ``codec`` overrides the psum
  trace) and assert the INTENDED contract key fires — and that unrelated
  families stay silent, so a regression can't hide behind a shotgun of
  cross-family noise.

Everything is static (abstract tracing/lowering on forced CPU devices in
subprocesses — the main pytest process is locked to 1 device); no step
ever executes.
"""
import json
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _run(code: str, timeout: int = 540) -> str:
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=ROOT, timeout=timeout)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    return r.stdout


PRELUDE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
from repro.analysis import contracts as CT

def keys(findings, severity=None):
    return sorted({f.key for f in findings
                   if severity is None or f.severity == severity})

def families(findings, severity="error"):
    return sorted({f.family for f in findings if f.severity == severity})
"""


# ---------------------------------------------------------------------------
# clean run: the registry agrees with reality under the current policy
# ---------------------------------------------------------------------------

def test_all_registered_specs_clean():
    """Zero error findings on every registered configuration — the same
    gate `python -m repro.analysis.lint --all` enforces in CI, minus the
    source-level passes (covered separately below)."""
    out = _run(PRELUDE + """
findings = CT.check_all()
errs = [f for f in findings if f.severity == "error"]
assert not errs, "\\n".join(f"{f.config}: [{f.key}] {f.message}"
                            for f in errs)
print("CLEAN_OK", len(CT.STEP_SPECS) + len(CT.PSUM_SPECS))
""", timeout=580)
    assert "CLEAN_OK 15" in out


def test_registry_and_spec_lookup():
    """Registry sanity without any tracing: specs list/lookup, contract
    keys are family.name with registered severities, psum/step split."""
    out = _run(PRELUDE + """
assert len(CT.STEP_SPECS) == 11 and len(CT.PSUM_SPECS) == 4
assert CT.get_spec("overlap").overlap is True
assert CT.get_spec("psum_int8_w4").bits == 8
try:
    CT.get_spec("nope")
except KeyError as e:
    assert "nope" in str(e)
else:
    raise AssertionError("unknown spec must KeyError")
for key, c in CT.CONTRACTS.items():
    fam, _, name = key.partition(".")
    assert name and c.severity in CT.SEVERITIES, key
assert CT.PSUM_CONTRACTS <= set(CT.CONTRACTS)
fams = {k.split(".")[0] for k in CT.CONTRACTS}
assert fams == {"dispatch", "schedule", "wire", "memory", "dtype",
                "cache"}, fams
print("REGISTRY_OK")
""")
    assert "REGISTRY_OK" in out


# ---------------------------------------------------------------------------
# mutation battery: one intended key per broken invariant
# ---------------------------------------------------------------------------

def test_mutation_memory_donation():
    """Tracing the `donate` spec with donation actually off must trip the
    memory family (donor markers + compiled aliasing) and nothing else."""
    out = _run(PRELUDE + """
f = CT.check_contracts("donate", overrides={"donate": False})
ks = keys(f, "error")
assert "memory.donation" in ks, ks
assert "memory.aliasing" in ks, ks
assert families(f) == ["memory"], families(f)
print("MUT_DONATE_OK")
""")
    assert "MUT_DONATE_OK" in out


def test_mutation_schedule_overlap():
    """The `overlap` spec traced with the paper-faithful ordering (the
    original silent-no-op bug) must trip the schedule family: no carried
    in-flight pair, ppermutes back on the critical path."""
    out = _run(PRELUDE + """
f = CT.check_contracts("overlap", overrides={"overlap": False})
ks = keys(f, "error")
assert "schedule.carried" in ks, ks
assert "schedule.work_to_consumer" in ks, ks
assert families(f) == ["schedule"], families(f)
print("MUT_OVERLAP_OK")
""")
    assert "MUT_OVERLAP_OK" in out


def test_mutation_schedule_health_and_faults():
    """Sentinel headers and the fault injector: dropping health from the
    `health` spec kills the header ppermutes (count + wire dtypes); a
    faults spec traced without its FaultPlan loses the xor machinery."""
    out = _run(PRELUDE + """
f = CT.check_contracts("health", overrides={"health": False,
                                            "faults": None})
ks = keys(f, "error")
assert "schedule.ppermute_count" in ks, ks
f = CT.check_contracts("faults", overrides={"faults": None})
ks = keys(f, "error")
assert ks == ["schedule.fault_injector"], ks
print("MUT_HEALTH_OK")
""")
    assert "MUT_HEALTH_OK" in out


def test_mutation_wire_dtypes_and_bytes():
    """Quantized-wire invariants: the int8_wire spec traced with a 16-bit
    q codec moves the wrong dtype AND the wrong byte count on the q edge —
    both wire contracts must name it; schedule stays silent (same
    ppermute count/ordering either way)."""
    out = _run(PRELUDE + """
from repro.comm.codecs import GridCodec
from repro.core.quantize import uniform_grid
f = CT.check_contracts(
    "int8_wire",
    overrides={"q_codec": GridCodec(uniform_grid(16, *CT.GRID_RANGE))})
ks = keys(f, "error")
assert "wire.dtypes" in ks, ks
assert "wire.ppermute_bytes" in ks, ks
assert families(f) == ["wire"], families(f)
print("MUT_WIRE_OK")
""")
    assert "MUT_WIRE_OK" in out


def test_mutation_dispatch_policy_flip():
    """The silent-ref-fallback scenario dispatch.pallas_calls exists for:
    pin the plan under REPRO_KERNELS=interpret (kernels expected), then
    flip the policy to ref before tracing — every pallas_call vanishes
    from the program and the contract must name the divergence."""
    out = _run(PRELUDE + """
os.environ["REPRO_KERNELS"] = "interpret"
view = CT.ProgramView(CT.get_spec("baseline"))
plan = view.plan                      # pinned: interpret-policy counts
assert plan.pallas_calls, plan
os.environ["REPRO_KERNELS"] = "ref"   # dispatch silently falls back
problems = list(CT.CONTRACTS["dispatch.pallas_calls"].check(view))
assert problems, "policy flip must be caught"
assert "pallas_call" in problems[0][0]
print("MUT_DISPATCH_OK")
""")
    assert "MUT_DISPATCH_OK" in out


def test_mutation_dispatch_fista_operands():
    """dispatch.fista_operands pins the z_L solve to one layer's logit
    columns. On a spec wider than one lane tile (h = 160 > 128 > C), a
    full-width solve and the stack-wide solve over every local layer's
    rows each trip exactly that key; the unmutated step is clean."""
    out = _run(PRELUDE + """
import dataclasses
import jax.numpy as jnp
os.environ["REPRO_KERNELS"] = "interpret"
from repro.core import subproblems as sp
from repro.parallel import stage_parallel as SP
wide = dataclasses.replace(CT.get_spec("baseline"), name="wide", h=160,
                           cache_probe=False, check_ragged=False)
assert keys(CT.check_contracts(wide), "error") == []
one_layer = SP._fista_last

def full_width(a, z_old, labels, mask, nu, n_classes, n_iters,
               use_kernels=True):
    return one_layer(a, z_old, labels, mask, nu, a.shape[-1], n_iters,
                     use_kernels)

def stack_wide(a, z_old, labels, mask, nu, n_classes, n_iters,
               use_kernels=True):
    m, h = a.shape[0], a.shape[-1]
    z = sp.update_z_last(a.reshape(-1, h), z_old.reshape(-1, h),
                         jnp.tile(labels, m), jnp.tile(mask, m), nu,
                         n_iters, n_classes=n_classes,
                         use_kernels=use_kernels)
    return z.reshape(a.shape)[-1]

for mutant in (full_width, stack_wide):
    SP._fista_last = mutant
    f = CT.check_contracts(wide)
    assert keys(f, "error") == ["dispatch.fista_operands"], keys(f, "error")
SP._fista_last = one_layer
print("MUT_FISTA_OK")
""")
    assert "MUT_FISTA_OK" in out


def test_mutation_dtype_f64_leak():
    """dtype.no_f64 must bite on a program with float64 avals. The global
    x64 switch breaks the step's own scan before any contract runs (carry
    dtype mismatch), so the leak is injected at the artifact level: the
    view's traced program is replaced with one containing a genuine f64
    upcast (built under the scoped enable_x64 context), the exact shape of
    the silent-promotion bug the contract guards against."""
    out = _run(PRELUDE + """
import jax, jax.numpy as jnp
view = CT.ProgramView(CT.get_spec("baseline"))
with jax.enable_x64():
    closed = jax.make_jaxpr(lambda x: x.astype(jnp.float64) * 2.0)(
        jax.ShapeDtypeStruct((4, 4), jnp.float32))
view._cache["traced"] = (None, None, (), closed)
problems = list(CT.CONTRACTS["dtype.no_f64"].check(view))
assert problems and "float64" in problems[0][0], problems

# the real traced program stays f64-clean (and strongly typed)
clean = CT.check_contracts("baseline", families=["dtype"])
assert not [f for f in clean if f.severity == "error"], clean
print("MUT_F64_OK")
""")
    assert "MUT_F64_OK" in out


def test_mutation_cache_family():
    """Cache-key contracts: a pinned set that disagrees with the real
    kwarg-only surface fails cache.kwarg_set; an identity variant (kwarg
    flip that changes nothing) fails cache.kwarg_observable with the
    kwarg named."""
    out = _run(PRELUDE + """
f = CT.check_contracts(
    "baseline", families=["cache"],
    pinned=sorted(CT.PINNED_STEP_KWARGS) + ["phantom_kwarg"])
ks = keys(f, "error")
assert "cache.kwarg_set" in ks, ks

f = CT.check_contracts("baseline", families=["cache"],
                       variants={"overlap": {}})   # identity "flip"
ks = keys(f, "error")
assert ks == ["cache.kwarg_observable"], ks
assert any("overlap" in x.message for x in f), f
print("MUT_CACHE_OK")
""")
    assert "MUT_CACHE_OK" in out


def test_mutation_psum_mode_and_bytes():
    """quantized_psum contracts, one key per mutation: a 16-bit codec on
    the int4 point moves the program from packed-gather to code_psum —
    exactly schedule.psum_mode (wire.psum_bytes defers when the
    collective itself is wrong); an 8-bit codec keeps the gather mode but
    moves the wrong number of packed bytes — exactly wire.psum_bytes."""
    out = _run(PRELUDE + """
from repro.comm.codecs import AffineCodec
f = CT.check_contracts("psum_int4_w4",
                       overrides={"codec": AffineCodec(16)})
assert keys(f, "error") == ["schedule.psum_mode"], keys(f, "error")

f = CT.check_contracts("psum_int4_w4",
                       overrides={"codec": AffineCodec(8)})
assert keys(f, "error") == ["wire.psum_bytes"], keys(f, "error")
print("MUT_PSUM_OK")
""")
    assert "MUT_PSUM_OK" in out


# ---------------------------------------------------------------------------
# CLI + source-level passes
# ---------------------------------------------------------------------------

def test_lint_cli_json_single_config():
    """`python -m repro.analysis.lint --config baseline --format=json`
    exits 0 with a well-formed zero-error report."""
    r = subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint", "--config",
         "baseline", "--format=json", "--no-examples", "--no-deadcode"],
        capture_output=True, text=True, cwd=ROOT, timeout=540,
        env={**__import__("os").environ, "PYTHONPATH": "src"})
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    report = json.loads(r.stdout)
    assert report["configs"] == ["baseline"]
    assert report["counts"]["error"] == 0
    assert report["policy"] in ("auto", "ref", "pallas", "interpret")
    assert isinstance(report["findings"], list)


def test_lint_cli_list():
    """--list names every registered spec and contract without tracing."""
    r = subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint", "--list"],
        capture_output=True, text=True, cwd=ROOT, timeout=120,
        env={**__import__("os").environ, "PYTHONPATH": "src"})
    assert r.returncode == 0, r.stderr[-3000:]
    assert "step  baseline" in r.stdout
    assert "psum  psum_int4_w4" in r.stdout
    assert "dispatch.pallas_calls" in r.stdout


def test_static_checks_examples_and_deadcode(tmp_path):
    """The source-level passes on synthetic trees: a stale kwarg and a
    stale import in examples/, an unused + duplicate import and an
    unreachable statement in src/repro/ — each yields its finding; the
    clean file yields none."""
    out = _run(PRELUDE + f"""
from repro.analysis import static_checks as SC
import os
root = {str(tmp_path)!r}
os.makedirs(os.path.join(root, "examples"))
os.makedirs(os.path.join(root, "src/repro"))
with open(os.path.join(root, "examples/demo.py"), "w") as fh:
    fh.write(
        "from repro.core.quantize import uniform_grid\\n"
        "from repro.core.quantize import no_such_symbol\\n"
        "uniform_grid(8, -2.0, 6.0, phantom_kwarg=1)\\n")
f = SC.check_examples(root)
ks = sorted({{x.key for x in f}})
assert ks == ["examples.import", "examples.stale_kwarg"], ks

with open(os.path.join(root, "src/repro/mod.py"), "w") as fh:
    fh.write(
        "import os\\n"
        "import json\\n"
        "import json\\n"
        "def f():\\n"
        "    return 1\\n"
        "    os.getcwd()\\n"
        "print(json.dumps([]))\\n")
f = SC.check_deadcode(root)
ks = sorted({{x.key for x in f}})
assert ks == ["deadcode.duplicate_import", "deadcode.unreachable"], ks
assert any(x.key == "deadcode.unreachable" for x in f)

with open(os.path.join(root, "src/repro/mod.py"), "w") as fh:
    fh.write("import os\\nprint(os.getcwd())\\n")
assert SC.check_deadcode(root) == []
print("STATIC_OK")
""")
    assert "STATIC_OK" in out


def test_deadcode_unused_import_and_ignores():
    """Unused imports are errors; `# noqa` lines, `__init__.py`, and the
    pinned DEADCODE_IGNORE patterns are exempt."""
    out = _run(PRELUDE + """
from repro.analysis import static_checks as SC
import os, tempfile
root = tempfile.mkdtemp()
os.makedirs(os.path.join(root, "src/repro/configs"))
with open(os.path.join(root, "src/repro/mod.py"), "w") as fh:
    fh.write("import os\\nimport sys  # noqa\\n")
with open(os.path.join(root, "src/repro/__init__.py"), "w") as fh:
    fh.write("import os\\n")
with open(os.path.join(root, "src/repro/configs/zoo.py"), "w") as fh:
    fh.write("import os\\n")
f = SC.check_deadcode(root)
assert [x.key for x in f] == ["deadcode.unused_import"], f
assert f[0].details["name"] == "os" and "mod.py" in f[0].config
print("DEADCODE_OK")
""")
    assert "DEADCODE_OK" in out
