"""Serve a small LM with batched requests through the continuous-batching
engine (reduced tinyllama config on CPU; the same engine/serve_step drives
the decode dry-run cells at production scale).

  PYTHONPATH=src python examples/serve_lm.py
"""
import jax

from repro.configs.base import ShapeConfig, get_arch
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.api import build
from repro.serve.engine import Request, ServingEngine


def main():
    enable_compile_cache()
    mesh = make_host_mesh()
    cfg = get_arch("tinyllama-1.1b").reduced()
    bundle = build(cfg, mesh, ShapeConfig("serve", 128, 4, "decode"))
    params = bundle.init(jax.random.PRNGKey(0))
    engine = ServingEngine(bundle, params, slots=4, max_len=128)

    requests = [Request(rid=i, prompt=[10 + i, 20 + i, 30 + i], max_new=12)
                for i in range(7)]          # 7 requests > 4 slots: queueing
    print(f"serving {len(requests)} requests on {engine.slots} slots ...")
    done = engine.run(requests)
    for rid in sorted(done):
        print(f"req {rid}: {done[rid]}")


if __name__ == "__main__":
    main()
