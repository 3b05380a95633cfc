"""Mini dry-run: the full launch path (lower + compile + stats extraction)
on an 8-device CPU mesh with a reduced arch — CI-sized proof that the
dry-run machinery works end to end."""
import json
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

CODE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys; sys.path.insert(0, "src")
import dataclasses, jax
from jax.sharding import NamedSharding, PartitionSpec as P
import repro.launch.mesh as M

# shrink the production mesh for the test
M.make_production_mesh = lambda multi_pod=False: M.make_mesh(
    (2, 2, 2) if multi_pod else (2, 4),
    ("pod", "data", "model") if multi_pod else ("data", "model"))

import repro.configs.tinyllama as TL
import repro.configs.base as CB
TL.CONFIG = dataclasses.replace(TL.CONFIG.reduced(), remat=True)
CB.SHAPES_BY_NAME = dict(CB.SHAPES_BY_NAME)
CB.SHAPES_BY_NAME["train_4k"] = CB.ShapeConfig("train_4k", 64, 4, "train")
CB.SHAPES_BY_NAME["decode_32k"] = CB.ShapeConfig("decode_32k", 64, 4, "decode")
import repro.launch.dryrun as D
D.SHAPES_BY_NAME = CB.SHAPES_BY_NAME

for shape, multi in (("train_4k", False), ("decode_32k", False),
                     ("train_4k", True)):
    compiled, meta = D.lower_cell("tinyllama-1.1b", shape, multi)
    stats = D.cell_stats(compiled, meta, 8)
    assert stats["flops_per_device"] > 0, (shape, stats)
    assert stats["memory"]["peak_live_bytes"] > 0
    assert "total" in stats["collectives"]
    print("OK", shape, "multi" if multi else "single",
          f"{stats['flops_per_device']:.2e}")
print("MINI_DRYRUN_OK")
"""


def test_mini_dryrun():
    r = subprocess.run([sys.executable, "-c", CODE], capture_output=True,
                       text=True, cwd=ROOT, timeout=540)
    assert "MINI_DRYRUN_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-3000:]
