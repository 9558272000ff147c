"""End-to-end driver: multi-tenant, multi-architecture LM serving through
the Hydra stack — first a single-node ``HydraPlatform`` (pre-warmed
runtime pool, colocation-aware placement), then a two-node
``HydraCluster`` (cross-node placement + adaptive pools) — with
continuous batching per function. ``--reduced`` serves each
architecture's tiny same-family config, so this runs on CPU.

  PYTHONPATH=src python examples/serve_multitenant.py
"""
import sys
import tempfile

sys.path.insert(0, ".")

from repro.core.executable_cache import configure_compile_cache
from repro.launch.serve import main

if __name__ == "__main__":
    configure_compile_cache()
    with tempfile.TemporaryDirectory() as snap_dir:
        print("=== single-node HydraPlatform ===")
        main(["--reduced", "--archs", "qwen2.5-3b,mamba2-780m",
              "--tenants", "4", "--requests", "24", "--slots", "4", "--max-new", "12",
              "--pool", "2", "--snapshot-dir", snap_dir])
    with tempfile.TemporaryDirectory() as snap_dir:
        print("=== two-node HydraCluster ===")
        main(["--reduced", "--archs", "qwen2.5-3b,mamba2-780m",
              "--tenants", "4", "--requests", "24", "--slots", "4", "--max-new", "12",
              "--nodes", "2", "--pool", "1", "--snapshot-dir", snap_dir])
