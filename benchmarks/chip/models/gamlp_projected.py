"""The projected GA-MLP that stage-parallel pdADMM-G trains: every layer
h x h, the first layer's input the K-hop augmentation projected to h by a
fixed random matrix (``inputs.py``), the classifier head in the first C
columns of the last layer's z.

* inputs: ``inputs.inputs_for``, ``(Xp, labels, train_mask)``;
* program: the no-controller training path as ``distributed_train`` runs
  it: ``init_stack`` jitted with the state sharded by the step's specs, the
  data sharded ``P("data")``, ``make_distributed_step(overlap=False,
  health=False)`` on the configuration's ``(data, model)`` mesh;
* reference: ``reference/<config["reference"]>.py`` from the same ``Xp``,
  on the default device;
* leaves: ``p``, ``W``, ``b``, ``z``, ``q``, ``u`` of every layer, ``p[0]``
  (the fixed ``Xp``) among them;
* FLOPs: ``counts.contraction_flops``, five V x h x h contractions a layer.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from counts import contraction_flops as _contraction_flops
from inputs import inputs_for, state_key
from repro.core.pdadmm import ADMMConfig
from repro.core.quantize import QuantGrid
from repro.launch.mesh import make_mesh
from repro.parallel import stage_parallel as SP

LEAVES = ("p", "W", "b", "z", "q", "u")


def inputs(config: dict, seed: int):
    return inputs_for(config, seed)


def admm_config(traffic: dict) -> ADMMConfig:
    g = traffic["grid"]
    grid = None if g is None else QuantGrid(float(g["lo"]), float(g["step"]),
                                            int(g["levels"]))
    return ADMMConfig(nu=traffic["nu"], rho=traffic["rho"],
                      tau0=traffic["tau0"], fista_iters=traffic["fista_iters"],
                      quantize_p=traffic["quantize_p"],
                      quantize_q=traffic["quantize_q"], grid=grid)


def program(cell, devices, data):
    """-> (step, its arguments on the mesh, start(seed) -> start state)."""
    cfg = cell.config
    L, C = int(cfg["layers"]), int(cfg["classes"])
    admm = admm_config(cell.traffic)
    mesh_shape = tuple(cfg["mesh"])
    mesh = make_mesh(mesh_shape, ("data", "model"),
                     devices=devices[:math.prod(mesh_shape)])
    # looked up on the module at each call: tests plant faults there
    step, specs = SP.make_distributed_step(mesh, L, C, admm, overlap=False,
                                           health=False)
    shard = lambda spec: NamedSharding(mesh, spec)
    args = tuple(jax.device_put(x, shard(P("data"))) for x in data)
    init = jax.jit(SP.init_stack, static_argnums=(2, 3),
                   out_shardings=jax.tree.map(shard, specs))
    return step, args, lambda seed: init(state_key(seed), args[0], L, admm)


def reference_params(ref, traffic: dict, n_classes: int):
    g = traffic["grid"]
    grid = None if g is None else ref.Grid(float(g["lo"]), float(g["step"]),
                                           int(g["levels"]))
    return ref.Params(nu=traffic["nu"], rho=traffic["rho"],
                      tau0=traffic["tau0"], fista_iters=traffic["fista_iters"],
                      n_classes=n_classes,
                      p_grid=grid if traffic["quantize_p"] else None,
                      q_grid=grid if traffic["quantize_q"] else None)


def reference(cell, seed: int, data, devices, dtype, iterate=None):
    """-> (the reference's start layers, step(layers) -> (layers, primal
    residual)). It fits one chip at pubmed's size, so it runs on the default
    device. ``iterate`` replaces the reference's iteration (faults)."""
    ref = cell.ref
    prm = reference_params(ref, cell.traffic, int(cell.config["classes"]))
    start = ref.init(state_key(seed), data.Xp, int(cell.config["layers"]),
                     prm, dtype)
    step = iterate or ref.iterate
    return start, lambda layers: step(layers, data.labels, data.train_mask,
                                      prm)


def leaves(state):
    """The program's state ([L, ...] leaves) as per-layer leaves."""
    return [{k: getattr(state, k)[l] for k in LEAVES}
            for l in range(len(state.W))]


def reference_leaves(layers):
    return [dict(layer._asdict()) for layer in layers]


def faults(cell):
    """The reference's iteration with a fault planted: ``half_batch`` (only
    the first half of the nodes is updated; b's mean is over that half),
    ``altered`` (the last layer's first logit of every node is raised by 1
    where it is produced)."""
    ref = cell.ref

    def half_batch(layers, labels, mask, prm):
        h = layers[0].p.shape[0] // 2
        top = [ref.Layer(l.p[:h], l.W, l.b, l.z[:h], l.q[:h], l.u[:h])
               for l in layers]
        new, res = ref.iterate(top, labels[:h], mask[:h], prm)
        del top
        join = lambda a, b: jnp.concatenate([a, b[h:]])
        return [ref.Layer(join(n.p, o.p), n.W, n.b, join(n.z, o.z),
                          join(n.q, o.q), join(n.u, o.u))
                for n, o in zip(new, layers)], res

    def altered(layers, labels, mask, prm):
        new, res = ref.iterate(layers, labels, mask, prm)
        last = new[-1]
        new[-1] = last._replace(z=last.z.at[:, 0].add(1.0))
        return new, res

    return {"half_batch": half_batch, "altered": altered}


def contraction_flops(config: dict) -> float:
    return _contraction_flops(config["nodes"], config["hidden"],
                              config["layers"])
