"""A GA-MLP as pdADMM-G publishes it, trained by the serial program
(``repro.core.pdadmm``): the first layer K·d -> h on the unprojected K-hop
augmentation Psi = [H, AH, ..., A^(K-1) H], the hidden layers h -> h, the
head h -> C. The harness's tests add it as new files to show that a model
needs no edit of the harness.

* inputs: the graph and features of ``inputs.py``, Psi [V, K·d] formed;
* program: ``pdadmm.init_state`` (jitted) and ``pdadmm.iterate`` on one
  device;
* reference: ``reference/<config["reference"]>.py`` from the same Psi;
* leaves: ``W``, ``b``, ``z`` of every layer, ``p`` of every layer but the
  first (``p[0]`` is Psi, never updated), ``q`` and ``u`` of every layer
  but the last (the serial state has none there);
* FLOPs: five contractions a layer, three in the first (no p-step), each
  2 V n_l n_(l+1).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from inputs import make_features, make_graph, seed_key, spmm, state_key
from repro.core import pdadmm
from repro.core.quantize import QuantGrid


class Data(NamedTuple):
    Psi: jax.Array         # [V, K d] float32
    labels: jax.Array      # [V] int32
    train_mask: jax.Array  # [V] float32


@functools.partial(jax.jit, static_argnames=(
    "n_nodes", "n_edges", "n_classes", "n_features", "n_train", "k_hops"))
def make_data(key, *, n_nodes, n_edges, n_classes, n_features, n_train,
              k_hops) -> Data:
    k_lab, k_graph, k_feat, k_perm = jax.random.split(key, 4)
    labels = jax.random.randint(k_lab, (n_nodes,), 0, n_classes)
    g = make_graph(k_graph, labels, n_nodes, n_edges, n_classes)
    hops = [make_features(k_feat, labels, n_classes, n_features)]
    for _ in range(k_hops - 1):
        hops.append(spmm(g, hops[-1]))
    train = jax.random.permutation(k_perm, n_nodes)[:n_train]
    mask = jnp.zeros((n_nodes,), jnp.float32).at[train].set(1.0)
    return Data(jnp.concatenate(hops, axis=1), labels.astype(jnp.int32),
                mask)


def inputs(config: dict, seed: int) -> Data:
    return make_data(
        jax.random.fold_in(seed_key(seed), 0), n_nodes=config["nodes"],
        n_edges=config["edges"], n_classes=config["classes"],
        n_features=config["features"], n_train=config["train_nodes"],
        k_hops=config["k_hops"])


def dims(config: dict) -> tuple:
    return ((config["k_hops"] * config["features"],)
            + (config["hidden"],) * (config["layers"] - 1)
            + (config["classes"],))


def admm_config(traffic: dict) -> pdadmm.ADMMConfig:
    g = traffic["grid"]
    grid = None if g is None else QuantGrid(float(g["lo"]), float(g["step"]),
                                            int(g["levels"]))
    return pdadmm.ADMMConfig(
        nu=traffic["nu"], rho=traffic["rho"], tau0=traffic["tau0"],
        fista_iters=traffic["fista_iters"], quantize_p=traffic["quantize_p"],
        quantize_q=traffic["quantize_q"], grid=grid)


def program(cell, devices, data):
    admm = admm_config(cell.traffic)
    step = functools.partial(pdadmm.iterate, config=admm)
    args = tuple(jax.device_put(x, devices[0]) for x in data)
    init = jax.jit(pdadmm.init_state, static_argnums=(2, 3))
    shape = dims(cell.config)
    return step, args, lambda seed: init(state_key(seed), args[0], shape,
                                         admm)


def reference(cell, seed: int, data, devices, dtype, iterate=None):
    admm = admm_config(cell.traffic)
    ref = cell.ref
    X = data.Psi.astype(dtype)
    start = ref.init(state_key(seed), X, dims(cell.config), admm)
    iterate = iterate or ref.iterate

    @jax.jit
    def step(state):
        new, metrics = iterate(state, X, data.labels, data.train_mask, admm)
        return new, metrics["residual"]

    return start, step


def leaves(state):
    L = len(state.W)
    out = []
    for l in range(L):
        layer = {"W": state.W[l], "b": state.b[l], "z": state.z[l]}
        if l:
            layer["p"] = state.p[l]
        if l < L - 1:
            layer.update(q=state.q[l], u=state.u[l])
        out.append(layer)
    return out


reference_leaves = leaves


def faults(cell):
    return {}


def contraction_flops(config: dict) -> float:
    n = dims(config)
    return float(sum((3 if l == 0 else 5) * 2 * config["nodes"] * n[l]
                     * n[l + 1] for l in range(len(n) - 1)))
