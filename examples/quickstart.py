"""Quickstart: train a GA-MLP on a synthetic Cora-like graph with pdADMM-G,
then with the quantized pdADMM-G-Q, and compare.

  PYTHONPATH=src python examples/quickstart.py
"""
import jax

from repro.core import pdadmm, quantize
from repro.core.pdadmm import ADMMConfig
from repro.graph.datasets import synthetic
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ds = synthetic("cora", scale=0.5)
    X = ds.augmented(k_hops=4)         # Psi = {I, A~, A~^2, A~^3}
    dims = [X.shape[1], 100, 100, 100, ds.n_classes]
    key = jax.random.PRNGKey(0)

    print("== pdADMM-G ==")
    cfg = ADMMConfig(nu=1e-2, rho=1.0)
    _, hist = pdadmm.train(key, X, ds.labels, ds.masks, dims, cfg, epochs=40)
    print(f"objective {hist['objective'][0]:.2f} -> {hist['objective'][-1]:.2f}")
    print(f"residual  {hist['residual'][-1]:.2e}")
    print(f"test acc  {hist['test_acc'][-1]:.3f}")

    print("\n== pdADMM-G-Q (8-bit p & q) ==")
    cfg_q = ADMMConfig(nu=1e-2, rho=1.0, quantize_p=True, quantize_q=True,
                       grid=quantize.uniform_grid(8, -2.0, 6.0))
    _, hist_q = pdadmm.train(key, X, ds.labels, ds.masks, dims, cfg_q,
                             epochs=40)
    print(f"objective {hist_q['objective'][0]:.2f} -> {hist_q['objective'][-1]:.2f}")
    print(f"test acc  {hist_q['test_acc'][-1]:.3f}")
    # wire bytes come from the CommLedger — the single source of truth
    from repro.comm.codecs import codec_for_grid
    from repro.comm.ledger import admm_bytes_per_iteration

    def bytes_per_iter(c):
        return admm_bytes_per_iteration(
            dims, X.shape[0],
            codec_for_grid(c.grid if c.quantize_p else None),
            codec_for_grid(c.grid if c.quantize_q else None))

    base, qb = bytes_per_iter(cfg), bytes_per_iter(cfg_q)
    print(f"comm bytes/iter: {base:.3e} -> {qb:.3e} "
          f"({100 * (1 - qb / base):.0f}% saved)")


if __name__ == "__main__":
    main()
