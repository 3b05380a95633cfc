"""CPU tests of the comparison that decides ``correct``, at a size a test
run can hold (V = 300, h = 128, L = 8; both jobs, and the limits of
``pubmed-L8.fp32``):

* the control, the reference computed in bfloat16 and put in the
  program's place, fails a cell's limits while the program passes them;
* a run driven through ``run.run_cell``, past the look for a chip, with
  the program's step broken underneath, comes out not correct: a step
  that returns its state unchanged, half of the nodes left out of the
  update (b's mean over the rest), and an answer (the last layer's
  logits) altered where it is produced;
* through ``models/gamlp_projected`` the harness builds the program the
  direct calls of the stage path build: the same lowered chunk program,
  inputs and start state.
"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]

import control  # noqa: E402
import run  # noqa: E402
from compare import judge  # noqa: E402

SMALL = dict(nodes=300, edges=1500, features=40, train_nodes=30, hidden=128)
SEED = 2 ** 31 + 17


def small_cell(traffic: str) -> run.Cell:
    """pubmed-L8.fp32's cell with the job ``traffic``, cut to SMALL."""
    cell = run.Cell("pubmed-L8.fp32")
    cell.config = dict(cell.config, **SMALL)
    cell.traffic = run.load_json(HERE / "traffic" / f"{traffic}.json")
    return cell


def broken_step(kind: str):
    """A ``make_distributed_step`` whose step carries one planted fault."""
    import jax
    import jax.numpy as jnp

    from repro.parallel import stage_parallel as SP
    make = SP.make_distributed_step

    def factory(*args, **kwargs):
        step, specs = make(*args, **kwargs)

        def unchanged(c, *a):
            return c, step(c, *a)[1]

        def half_batch(c, Xp, lab, msk):
            h = Xp.shape[0] // 2
            top = jax.tree.map(lambda x: x[:, :h] if x.ndim == 3 else x, c)
            new, m = step(top, Xp[:h], lab[:h], msk[:h])
            join = lambda n, o: (jnp.concatenate([n, o[:, h:]], axis=1)
                                 if o.ndim == 3 else n)
            return jax.tree.map(join, new, c), m

        def altered(c, *a):
            new, m = step(c, *a)
            return new._replace(z=new.z.at[-1, :, 0].add(1.0)), m

        return {"unchanged": unchanged, "half_batch": half_batch,
                "altered": altered}[kind], specs
    return factory


def run_faulty(cell, fault, monkeypatch):
    import jax
    from repro.parallel import stage_parallel as SP
    if fault is not None:
        monkeypatch.setattr(SP, "make_distributed_step", broken_step(fault))
    return run.run_cell(cell, SEED, 0.01, False, jax.devices()[:cell.chips],
                        log=lambda s: None)


@pytest.mark.parametrize("traffic", ["fp32", "int8p"])
def test_control_fails_the_limits_that_the_program_meets(traffic):
    import jax
    cell = small_cell(traffic)
    out = control.readings(cell, SEED, jax.devices()[:1], faults=False,
                           log=lambda s: None)
    assert judge(out["program"], cell.limits)[0], out["program"]
    assert not judge(out["control"], cell.limits)[0], out["control"]


def parent_program(cell, seed, devices):
    """``pubmed-L8``'s program as the harness built it before models had
    modules of their own: the direct calls of ``run.Program``."""
    import math

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from inputs import inputs_for, seed_key
    from repro.core.pdadmm import ADMMConfig
    from repro.launch.mesh import make_mesh
    from repro.parallel import stage_parallel as SP
    cfg, t = cell.config, cell.traffic
    L, C = int(cfg["layers"]), int(cfg["classes"])
    admm = ADMMConfig(nu=t["nu"], rho=t["rho"], tau0=t["tau0"],
                      fista_iters=t["fista_iters"],
                      quantize_p=t["quantize_p"], quantize_q=t["quantize_q"],
                      grid=None)
    mesh_shape = tuple(cfg["mesh"])
    mesh = make_mesh(mesh_shape, ("data", "model"),
                     devices=devices[:math.prod(mesh_shape)])
    data = jax.block_until_ready(inputs_for(cfg, seed))
    step, specs = SP.make_distributed_step(mesh, L, C, admm, overlap=False,
                                           health=False)
    shard = lambda spec: NamedSharding(mesh, spec)
    args = tuple(jax.device_put(x, shard(P("data"))) for x in data)
    init = jax.jit(SP.init_stack, static_argnums=(2, 3),
                   out_shardings=jax.tree.map(shard, specs))
    state = init(jax.random.fold_in(seed_key(seed), 1), args[0], L, admm)
    return data, step, args, state


def test_pubmed_builds_the_parents_program():
    """Through ``models/gamlp_projected`` the window's chunk program lowers
    to the text of the direct calls (locations aside), from equal inputs
    and an equal start state."""
    import jax
    import numpy as np

    from repro.core import pdadmm
    cell = small_cell("fp32")
    devices = jax.devices()[:cell.chips]
    prog = run.Program(cell, SEED, devices, log=lambda s: None)
    data, step, args, state = parent_program(cell, SEED, devices)
    lower = lambda st, a, f: pdadmm._scan_chunk.lower(
        st, a, step_fn=f, length=prog.chunk).as_text(debug_info=False)
    mine = lower(prog.state, prog.args, prog.step)
    assert "admm.p" not in mine       # no location or scope metadata left
    assert mine == lower(state, args, step)
    for a, b in zip(jax.tree.leaves((prog.inputs, prog.args, prog.state)),
                    jax.tree.leaves((data, args, state)), strict=True):
        assert a.sharding == b.sharding
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("fault", [None, "unchanged", "half_batch",
                                   "altered"])
def test_a_broken_step_is_not_correct(fault, monkeypatch):
    cell = small_cell("fp32")
    result = run_faulty(cell, fault, monkeypatch)
    assert result["correct"] is (fault is None), result["compared"]
    assert list(result)[-2:] == ["compared", "_lines"]
