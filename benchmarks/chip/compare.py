"""The comparison that decides ``correct``: the program's state after the
first chunk of the window's own program against the plain reference.

Readings, each a relative gap against the reference:

* ``logits``: ||a - b|| / ||b|| of the labelled nodes' logits, the first C
  columns of the last layer's z: the model's answer on the nodes the loss
  sees;
* ``risk``: |a - b| / |b| of the loss, the summed cross-entropy of those
  logits;
* one per state leaf (``p``, ``W``, ``b``, ``z``, ``q``, ``u``, as the
  model module names them): ||a - b|| / ||b|| over the whole leaf, all
  layers that have it;
* ``z_tail``: the same over the last ``V mod 256`` rows of every layer's z,
  the masked edge block of the z-update kernel's 256-row tile, which a
  whole-leaf norm would dilute;
* ``residual``: the largest |a_k - b_k| over the chunk's iterations k of
  the primal residual sqrt(sum_l ||p_{l+1} - q_l||^2), over the largest
  b_k;
* ``move``: how far the chunk moved each state leaf (over all layers
  that have it) from its own start, the program's from the program's start
  and the reference's from the reference's: the worst leaf's gap between
  the two norms of the change, | ||a - a0|| - ||b - b0|| |, over the
  reference's norm of that change or of the median leaf's, whichever is
  larger. A leaf that the
  reference moves by less than ``STILL`` of the median leaf's change is
  left out. ``move.<leaf>`` is the same for one leaf.

The state gaps compare where the two iterates end; ``move`` compares how
far each went, so a start that rounds differently does not hide a state
that never moved.

Both sides come as the model module's ``leaves`` and ``reference_leaves``
give them: one mapping of leaf name to array per layer, on the host or the
device. A layer compares the leaves its reference mapping holds.

A cell's limits file names the readings that decide ``correct``; the others
are printed for the record (PERF.md says why each is or is not compared).
"""
from __future__ import annotations

import math
from collections import defaultdict
from typing import Dict, List, Mapping, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Layers = Sequence[Mapping[str, object]]
EDGE_TILE = 256
STILL = 1e-3


def tail_rows(n_nodes: int) -> int:
    return n_nodes % EDGE_TILE or EDGE_TILE


def gaps(program: Layers, reference: Layers, program_res: Sequence[float],
         reference_res: Sequence[float], labels, train_mask,
         n_classes: int, program_start: Layers = None,
         reference_start: Layers = None) -> Dict[str, float]:
    """Each side's leaves layer by layer (see the module's docstring).
    ``move`` is read when both starts are given. The sums run on the device
    in float32, one layer at a time."""
    train = np.flatnonzero(np.asarray(train_mask))
    lab = jnp.asarray(np.asarray(labels)[train])
    a = jnp.asarray(program[-1]["z"], jnp.float32)[train, :n_classes]
    b = jnp.asarray(reference[-1]["z"])[train, :n_classes].astype(
        jnp.float32)
    num, den = _sq_sums(a, b)
    risk_a, risk_b = _risk(a, lab), _risk(b, lab)
    out = {"logits": math.sqrt(float(num) / float(den)),
           "risk": abs(float(risk_a) - float(risk_b)) / abs(float(risk_b))}
    a_res = np.asarray(program_res, np.float64)
    b_res = np.asarray(reference_res, np.float64)
    out["residual"] = float(np.max(np.abs(a_res - b_res))
                            / np.max(np.abs(b_res)))
    sums = defaultdict(lambda: [0.0, 0.0])
    for prog_layer, ref_layer in zip(program, reference, strict=True):
        for k, b in ref_layer.items():
            b = jnp.asarray(b)
            a = jnp.asarray(prog_layer[k], jnp.float32)
            num, den = _sq_sums(a, b)
            sums[k][0] += float(num)
            sums[k][1] += float(den)
            if k == "z":
                t = tail_rows(a.shape[0])
                num, den = _sq_sums(a[-t:], b[-t:])
                sums["z_tail"][0] += float(num)
                sums["z_tail"][1] += float(den)
    for k, (num, den) in sums.items():
        out[k] = math.sqrt(num / den) if den > 0 else math.inf
    if program_start is not None and reference_start is not None:
        out.update(moves(program, program_start, reference, reference_start))
    return out


def change_norms(program: Layers, program_start: Layers, reference: Layers,
                 reference_start: Layers) -> List[Tuple[str, int, float,
                                                        float]]:
    """(leaf, layer, ||a - a0||, ||b - b0||) for every leaf of every layer
    that the reference holds."""
    norms = []
    for l, (a, a0, ref, ref0) in enumerate(zip(
            program, program_start, reference, reference_start, strict=True)):
        for k in ref:
            da, db = _change(jnp.asarray(a[k]), jnp.asarray(a0[k]),
                             jnp.asarray(ref[k]), jnp.asarray(ref0[k]))
            norms.append((k, l, float(da), float(db)))
    return norms


def moves(program: Layers, program_start: Layers, reference: Layers,
          reference_start: Layers) -> Dict[str, float]:
    """``move`` and ``move.<leaf>`` (see the module's docstring)."""
    sq = defaultdict(lambda: [0.0, 0.0])
    for k, _, da, db in change_norms(program, program_start, reference,
                                     reference_start):
        sq[k][0] += da * da
        sq[k][1] += db * db
    norms = {k: (math.sqrt(a), math.sqrt(b)) for k, (a, b) in sq.items()}
    median = float(np.median([db for _, db in norms.values()]))
    out = {"move": 0.0}
    for k, (da, db) in norms.items():
        g = 0.0 if db <= STILL * median else abs(da - db) / max(db, median)
        out[f"move.{k}"] = g
        out["move"] = max(out["move"], g)
    return out


@jax.jit
def _change(a, a0, b, b0):
    f = lambda x, x0: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32) - x0.astype(jnp.float32))))
    return f(a, a0), f(b, b0)


@jax.jit
def _risk(logits, labels):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=-1))


@jax.jit
def _sq_sums(a, b):
    b = b.astype(jnp.float32)
    return jnp.sum(jnp.square(a - b)), jnp.sum(jnp.square(b))


def judge(values: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, List[str]]:
    """True when every number is finite and within its limit; one line per
    number, ``name value (limit x)``."""
    ok, lines = True, []
    for name, limit in limits.items():
        v = values.get(name, math.nan)
        good = math.isfinite(v) and v <= limit
        ok &= good
        lines.append(f"{name} {v:.6e} (limit {limit:g})"
                     + ("" if good else " OVER"))
    return ok, lines
