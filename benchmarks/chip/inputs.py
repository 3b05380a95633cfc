"""Inputs of a cell, made on the device from the seed in one jitted call.

The graph follows the program's synthetic Table-II generator
(``repro.graph.datasets.synthetic``), rewritten here so that the yardstick
does not move with the program and so that set-up builds it on the chip:

* ``V`` nodes with uniform class labels in ``[0, C)``;
* ``E`` directed edges, 75% from a node to a node of its own class and 25%
  uniform; symmetrised, self loops added, duplicates dropped, and weighted
  ``D^-1/2 (A + I) D^-1/2`` (Kipf-Welling renormalisation);
* sparse class-dependent features: each class owns 32 distinct columns of
  ``d``; a node's row holds its class's means plus 0.8 N(0, 1) noise on
  those columns and zero elsewhere, normalised to unit L1 norm;
* ``n_train`` labelled nodes drawn uniformly.

The GA-MLP input is the ``K``-hop augmentation ``X = [H, AH, ..., A^(K-1) H]``
projected to width ``h`` by a fixed random ``P0 ~ N(0, 2 / (K d))`` and
passed through ReLU, ``relu(X @ P0)``, unscaled, as the program's own
callers build it. ``X`` itself is never formed: each hop's block of ``P0``
is applied first, ``Y_k = H P0_k``, and the hops are summed by Horner's
rule, ``relu(Y_0 + A (Y_1 + A (Y_2 + A Y_3)))``. That equals
``relu(X @ P0)`` in exact arithmetic, and propagates ``[V, h]`` blocks
instead of ``[V, K d]`` ones.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

SIGNAL_COLS = 32        # feature columns per class (the program's choice)
INTRA_SHARE = 0.75      # share of edges that stay inside a class
NOISE = 0.8             # feature noise scale
HIGHEST = jax.lax.Precision.HIGHEST


class Graph(NamedTuple):
    """Renormalised adjacency in COO form, sorted by (src, dst). Duplicate
    entries carry weight 0."""
    src: jax.Array
    dst: jax.Array
    weight: jax.Array


def seed_key(seed: int) -> jax.Array:
    """A PRNG key that keeps every bit of a seed wider than 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def state_key(seed: int) -> jax.Array:
    """The key of a model's start state (weights), apart from its inputs'."""
    return jax.random.fold_in(seed_key(seed), 1)


def make_graph(key, labels, n_nodes: int, n_edges: int, n_classes: int
               ) -> Graph:
    V, E, C = n_nodes, n_edges, n_classes
    k_src, k_dst, k_rs, k_rd = jax.random.split(key, 4)
    n_intra = int(INTRA_SHARE * E)
    order = jnp.argsort(labels, stable=True)
    sorted_lab = labels[order]
    starts = jnp.searchsorted(sorted_lab, jnp.arange(C), side="left")
    ends = jnp.searchsorted(sorted_lab, jnp.arange(C), side="right")
    src_i = jax.random.randint(k_src, (n_intra,), 0, V)
    lab_i = labels[src_i]
    span = jnp.maximum(ends - starts, 1)[lab_i]
    pick = jax.random.randint(k_dst, (n_intra,), 0, 1 << 30) % span
    dst_i = order[jnp.minimum(starts[lab_i] + pick, V - 1)]
    src_r = jax.random.randint(k_rs, (E - n_intra,), 0, V)
    dst_r = jax.random.randint(k_rd, (E - n_intra,), 0, V)
    src = jnp.concatenate([src_i, src_r])
    dst = jnp.concatenate([dst_i, dst_r])
    loops = jnp.arange(V)
    s = jnp.concatenate([src, dst, loops]).astype(jnp.int32)
    d = jnp.concatenate([dst, src, loops]).astype(jnp.int32)
    s, d = jax.lax.sort((s, d), num_keys=2)
    first = jnp.concatenate([jnp.ones((1,), bool),
                             (s[1:] != s[:-1]) | (d[1:] != d[:-1])])
    keep = first.astype(jnp.float32)
    deg = jax.ops.segment_sum(keep, s, num_segments=V, indices_are_sorted=True)
    dinv = jax.lax.rsqrt(deg)
    return Graph(s, d, dinv[s] * dinv[d] * keep)


def make_features(key, labels, n_classes: int, n_features: int):
    """Dense [V, d] feature rows with SIGNAL_COLS non-zeros each."""
    V, C, D = labels.shape[0], n_classes, n_features
    sig = min(SIGNAL_COLS, D)
    k_means, k_cols, k_noise = jax.random.split(key, 3)
    means = jax.random.normal(k_means, (C, sig))
    cols = jax.vmap(lambda k: jax.random.permutation(k, D)[:sig])(
        jax.random.split(k_cols, C))
    vals = means[labels] + NOISE * jax.random.normal(k_noise, (V, sig))
    vals = vals / jnp.maximum(jnp.sum(jnp.abs(vals), axis=1, keepdims=True),
                              1e-9)
    rows = jnp.broadcast_to(jnp.arange(V)[:, None], (V, sig))
    return jnp.zeros((V, D), jnp.float32).at[rows, cols[labels]].set(vals)


def spmm(g: Graph, x):
    """A @ x for the symmetric renormalised adjacency."""
    msgs = x[g.dst] * g.weight[:, None]
    return jax.ops.segment_sum(msgs, g.src, num_segments=x.shape[0],
                               indices_are_sorted=True)


def project_hops(g: Graph, H, P0, k_hops: int):
    """relu(sum_k A^k H P0_k), P0 = [P0_0; ...; P0_{K-1}] by rows."""
    d = H.shape[1]
    acc = None
    for k in reversed(range(k_hops)):
        y = jnp.dot(H, P0[k * d:(k + 1) * d], precision=HIGHEST)
        acc = y if acc is None else y + spmm(g, acc)
    return jnp.maximum(acc, 0.0)


class Inputs(NamedTuple):
    Xp: jax.Array          # [V, h] float32, the projected GA-MLP input
    labels: jax.Array      # [V] int32
    train_mask: jax.Array  # [V] float32, 1 on the labelled nodes


@functools.partial(jax.jit, static_argnames=(
    "n_nodes", "n_edges", "n_classes", "n_features", "n_train", "k_hops",
    "hidden"))
def make_inputs(key, *, n_nodes, n_edges, n_classes, n_features, n_train,
                k_hops, hidden) -> Inputs:
    k_lab, k_graph, k_feat, k_perm, k_proj = jax.random.split(key, 5)
    labels = jax.random.randint(k_lab, (n_nodes,), 0, n_classes)
    g = make_graph(k_graph, labels, n_nodes, n_edges, n_classes)
    H = make_features(k_feat, labels, n_classes, n_features)
    n_in = k_hops * n_features
    P0 = jax.random.normal(k_proj, (n_in, hidden)) * jnp.sqrt(2.0 / n_in)
    Xp = project_hops(g, H, P0, k_hops)
    train = jax.random.permutation(k_perm, n_nodes)[:n_train]
    mask = jnp.zeros((n_nodes,), jnp.float32).at[train].set(1.0)
    return Inputs(Xp, labels.astype(jnp.int32), mask)


def inputs_for(config: dict, seed: int) -> Inputs:
    """The cell's inputs from a configuration file's sizes and the seed."""
    return make_inputs(
        jax.random.fold_in(seed_key(seed), 0),
        n_nodes=config["nodes"], n_edges=config["edges"],
        n_classes=config["classes"], n_features=config["features"],
        n_train=config["train_nodes"], k_hops=config["k_hops"],
        hidden=config["hidden"])
