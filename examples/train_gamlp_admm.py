"""End-to-end driver: greedy layerwise pdADMM-G training of a ~100M-param
GA-MLP for a few hundred iterations, with checkpointing and restart.

The 10x1000-neuron GA-MLP on the full augmented feature width is the paper's
Section V-C configuration; at k*d = 4x1433 inputs and |V|=2485 this is
~10M params — pass --hidden 4000 for the paper's large 4000-neuron /
~130M-param variant (slower on CPU).

  PYTHONPATH=src python examples/train_gamlp_admm.py --epochs 200
  # kill it mid-run, run again: resumes from the latest checkpoint
"""
import argparse
import functools
import time

import jax

from repro.ckpt.manager import CheckpointManager
from repro.core import pdadmm
from repro.core.pdadmm import ADMMConfig
from repro.graph.datasets import synthetic
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=200)
    ap.add_argument("--hidden", type=int, default=1000)
    ap.add_argument("--layers", type=int, default=10)
    ap.add_argument("--dataset", default="cora")
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--ckpt-dir", default="artifacts/ckpt_gamlp")
    ap.add_argument("--ckpt-every", type=int, default=25)
    args = ap.parse_args()

    ds = synthetic(args.dataset, scale=args.scale)
    X = ds.augmented(4)
    dims = [X.shape[1]] + [args.hidden] * (args.layers - 1) + [ds.n_classes]
    n_params = sum(dims[i] * dims[i + 1] + dims[i + 1]
                   for i in range(len(dims) - 1))
    print(f"dataset={ds.name} |V|={X.shape[0]} input={X.shape[1]} "
          f"params={n_params/1e6:.1f}M")

    cfg = ADMMConfig(nu=1e-2, rho=1.0)
    mgr = CheckpointManager(args.ckpt_dir, keep=2)
    state = pdadmm.init_state(jax.random.PRNGKey(0), X, dims, cfg)
    start = 0
    if mgr.latest_step() is not None:
        state, manifest = mgr.restore(state)
        state = pdadmm.ADMMState(*state)
        start = manifest["step"] + 1
        print(f"resumed from step {start}")

    step = jax.jit(functools.partial(pdadmm.iterate, config=cfg))
    t0 = time.time()
    for e in range(start, args.epochs):
        state, m = step(state, X, ds.labels, ds.masks["train"])
        if e % 10 == 0:
            print(f"epoch {e:4d} objective {float(m['objective']):.3e} "
                  f"residual {float(m['residual']):.3e} "
                  f"({time.time()-t0:.0f}s)", flush=True)
        if (e + 1) % args.ckpt_every == 0:
            mgr.save(e, tuple(state))
    acc = pdadmm.forward_accuracy(state, X, ds.labels, ds.masks["test"])
    print(f"final test accuracy: {float(acc):.3f}")


if __name__ == "__main__":
    main()
