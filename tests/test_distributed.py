"""Distributed runtime tests — run in subprocesses with forced multi-device
CPU (the main pytest process is locked to 1 device)."""
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


def _run(code: str) -> str:
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=ROOT, timeout=540)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    return r.stdout


PRELUDE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
"""


def test_stage_parallel_admm_converges():
    out = _run(PRELUDE + """
from repro.graph.datasets import tiny
from repro.core.pdadmm import ADMMConfig
from repro.parallel import stage_parallel as SP
ds = tiny(V=128)
X = ds.augmented(4)
key = jax.random.PRNGKey(0)
P0 = jax.random.normal(key, (X.shape[1], 64)) * jnp.sqrt(2.0 / X.shape[1])
Xp = jnp.maximum(X @ P0, 0)
cfg = ADMMConfig(nu=1e-2, rho=1.0)
st, hist = SP.distributed_train(mesh, key, Xp, ds.labels, ds.masks, 8,
                                ds.n_classes, cfg, epochs=20)
obj = hist["objective"]
assert obj[-1] < obj[0], obj
viol = sum(1 for a, b in zip(obj, obj[1:]) if b > a + 1e-4 * abs(a))
assert viol == 0, (viol, obj)
assert hist["residual"][-1] < 0.05
print("STAGE_OK")
""")
    assert "STAGE_OK" in out


def test_stage_parallel_matches_math_of_reference():
    """The distributed homogeneous variant must satisfy Lemma 4 too."""
    out = _run(PRELUDE + """
from repro.graph.datasets import tiny
from repro.core.pdadmm import ADMMConfig
from repro.parallel import stage_parallel as SP
ds = tiny(V=128)
X = ds.augmented(4)
key = jax.random.PRNGKey(0)
P0 = jax.random.normal(key, (X.shape[1], 64)) * jnp.sqrt(2.0 / X.shape[1])
Xp = jnp.maximum(X @ P0, 0)
cfg = ADMMConfig(nu=1e-2, rho=1.0)
st, _ = SP.distributed_train(mesh, key, Xp, ds.labels, ds.masks, 8,
                             ds.n_classes, cfg, epochs=5)
# Lemma 4 on the stacked hidden layers: u_l = nu (q_l - relu(z_l)), l < L-1
u = np.asarray(jax.device_get(st.u))[:-1]
q = np.asarray(jax.device_get(st.q))[:-1]
z = np.asarray(jax.device_get(st.z))[:-1]
rhs = cfg.nu * (q - np.maximum(z, 0))
err = np.abs(u - rhs).max()
assert err < 1e-5, err
print("LEMMA4_DIST_OK")
""")
    assert "LEMMA4_DIST_OK" in out


def test_stage_mesh_matches_one_device_f64():
    """Splitting the layers over four stages leaves pdADMM's iteration map
    unchanged: in float64, a (1, 4) mesh and a (1, 1) mesh reach the same
    state, leaf by leaf, with the fp32 and the grid-coded wire."""
    out = _run(PRELUDE + """
jax.config.update("jax_enable_x64", True)
from repro.configs.gamlp_paper import GAMLP
from repro.core.pdadmm import ADMMConfig
from repro.core.quantize import integer_grid
from repro.graph.datasets import synthetic
from repro.parallel import stage_parallel as SP
ds = synthetic("pubmed", seed=0, scale=0.02)          # V = 394, ragged
X = ds.augmented(4)
key = jax.random.PRNGKey(0)
P0 = jax.random.normal(key, (X.shape[1], 64)) * jnp.sqrt(2.0 / X.shape[1])
Xp = jnp.maximum(X @ P0, 0).astype(jnp.float64)
base = dict(nu=GAMLP.nu, rho=GAMLP.rho, fista_iters=GAMLP.fista_iters)
grid = integer_grid(min(GAMLP.quant_levels), max(GAMLP.quant_levels))
devs = jax.devices()
meshes = [make_mesh(s, ("data", "model"), devices=devs[:s[1]])
          for s in ((1, 4), (1, 1))]
for cfg in (ADMMConfig(**base),
            ADMMConfig(**base, quantize_p=GAMLP.quantize_p,
                       quantize_q=GAMLP.quantize_q, grid=grid)):
    finals = []
    for m in meshes:
        st = jax.tree.map(lambda a: a.astype(jnp.float64),
                          SP.init_stack(key, Xp, 8, cfg))
        step, _ = SP.make_distributed_step(m, 8, ds.n_classes, cfg)
        for _ in range(6):
            st, _ = step(st, Xp, ds.labels, ds.masks["train"])
        finals.append(jax.device_get(st))
    for name, a, b in zip(finals[1]._fields, *finals):
        assert a.dtype == np.float64, name
        gap = np.linalg.norm(a - b)
        assert gap <= 1e-12 * np.linalg.norm(b), (name, gap)
print("MESH_F64_OK")
""")
    assert "MESH_F64_OK" in out


def test_z_last_solved_on_one_layer_f64():
    """Each stage solves z_L on its last local layer only, and only the
    stage holding layer L-1 keeps the result: after one step in float64,
    on a (1, 1) and a (1, 4) mesh, layer L-1's z is the reference FISTA
    solve of its a (the C logit columns; the rest as the prox-only solve)
    and every other layer's z is the z-hidden update."""
    out = _run(PRELUDE + """
jax.config.update("jax_enable_x64", True)
from repro.core import subproblems as sp
from repro.core.pdadmm import ADMMConfig
from repro.graph.datasets import synthetic
from repro.parallel import stage_parallel as SP
ds = synthetic("pubmed", seed=0, scale=0.02)          # V = 394, ragged
X = ds.augmented(4)
key = jax.random.PRNGKey(0)
P0 = jax.random.normal(key, (X.shape[1], 64)) * jnp.sqrt(2.0 / X.shape[1])
Xp = jnp.maximum(X @ P0, 0).astype(jnp.float64)
L, C = 8, ds.n_classes
cfg = ADMMConfig(nu=1e-2, rho=1.0, fista_iters=15,
                 use_kernels=False)                   # every solve in f64
labels, mask = ds.labels, ds.masks["train"]
no_labels, no_mask = jnp.zeros_like(labels), jnp.zeros_like(mask)
devs = jax.devices()
for shape in ((1, 1), (1, 4)):
    m = make_mesh(shape, ("data", "model"), devices=devs[:shape[1]])
    st0 = jax.tree.map(lambda a: a.astype(jnp.float64),
                       SP.init_stack(key, Xp, L, cfg))
    step, _ = SP.make_distributed_step(m, L, C, cfg)
    st1, _ = step(st0, Xp, labels, mask)
    st0, st1 = jax.device_get(st0), jax.device_get(st1)
    for l in range(L):
        a = st1.p[l] @ st1.W[l] + st1.b[l]           # = z_old - r, exact
        if l == L - 1:
            want = jnp.concatenate([
                sp.update_z_last_reference(a[:, :C], st0.z[l][:, :C], labels,
                                           mask, cfg.nu, cfg.fista_iters),
                sp.update_z_last_reference(a[:, C:], st0.z[l][:, C:],
                                           no_labels, no_mask, cfg.nu,
                                           cfg.fista_iters)], axis=1)
        else:
            want = sp._zupdate(a, st0.q[l], st0.z[l], cfg.nu, False)
        gap = np.linalg.norm(st1.z[l] - want) / np.linalg.norm(want)
        assert gap < 1e-10, (shape, l, gap)
print("ZLAST_ONE_LAYER_OK")
""")
    assert "ZLAST_ONE_LAYER_OK" in out


def test_quantized_wire_reduces_ppermute_bytes():
    """HLO proof of the paper's claim: int8 wire shrinks collective-permute
    payloads 4x vs fp32."""
    out = _run(PRELUDE + """
from repro.core.pdadmm import ADMMConfig
from repro.core import quantize
from repro.parallel import stage_parallel as SP
from repro.analysis import hlo as H
V, h, L, C = 256, 64, 8, 4
labels = jnp.zeros((V,), jnp.int32)
mask = jnp.ones((V,))
def lower_bytes(cfg):
    step, specs = SP.make_distributed_step(mesh, L, C, cfg)
    Xp = jax.ShapeDtypeStruct((V, h), jnp.float32)
    st = jax.eval_shape(lambda k: SP.init_stack(k, jnp.zeros((V, h)), L, cfg),
                        jax.random.PRNGKey(0))
    lowered = step.lower(st, Xp, jax.ShapeDtypeStruct((V,), jnp.int32),
                         jax.ShapeDtypeStruct((V,), jnp.float32))
    txt = lowered.compile().as_text()
    stats = H.analyze(txt, 8)
    return stats.coll_summary()["by_kind"].get("collective-permute",
                                               {"payload_bytes": 0})
fp = lower_bytes(ADMMConfig(nu=1e-2, rho=1.0))
g8 = quantize.uniform_grid(8, -2., 6.)
q8 = lower_bytes(ADMMConfig(nu=1e-2, rho=1.0, quantize_p=True,
                            quantize_q=True, grid=g8))
print("fp payload:", fp["payload_bytes"], "q8 payload:", q8["payload_bytes"])
assert q8["payload_bytes"] < fp["payload_bytes"] * 0.62  # p,q int8; u fp32
print("WIRE_OK")
""")
    assert "WIRE_OK" in out


def test_quantized_psum_error_feedback():
    out = _run(PRELUDE + """
from repro.parallel.collectives import psum_with_error_feedback
from jax import shard_map
from jax.sharding import PartitionSpec as P

def f(x, e):
    s, ne = psum_with_error_feedback(x, e, "data", bits=8)
    return s, ne

sm = shard_map(f, mesh=mesh, in_specs=(P("data"), P("data")),
               out_specs=(P("data"), P("data")), check_vma=False)
x = jax.random.normal(jax.random.PRNGKey(0), (8, 32))
e = jnp.zeros_like(x)
s, ne = sm(x, e)
# compare against exact psum: each data row-block sums over 2 shards
exact = x.reshape(2, 4, 32).sum(0)
got = np.asarray(s).reshape(2, 4, 32)[0]
err0 = np.abs(np.asarray(got) - np.asarray(exact)).max()
assert err0 < 0.1, err0          # int8 quantization error, bounded
# error feedback: carried residual reduces bias over repeated rounds
tot_exact = np.zeros((4, 32)); tot_got = np.zeros((4, 32))
e = jnp.zeros_like(x)
for i in range(20):
    s, e = sm(x, e)
    tot_exact += np.asarray(exact)
    tot_got += np.asarray(s).reshape(2, 4, 32)[0]
drift = np.abs(tot_got - tot_exact).max() / 20
assert drift < err0 + 1e-6, (drift, err0)   # no accumulating bias
print("EF_OK")
""")
    assert "EF_OK" in out
