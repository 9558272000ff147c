"""Quickstart: one Hydra runtime, many functions, many languages-worth of
architectures.

  PYTHONPATH=src python examples/quickstart.py
"""
import sys

sys.path.insert(0, "benchmarks")
sys.path.insert(0, ".")

from benchmarks.functions import catalog, example_args
from repro.configs import get_config
from repro.core import HydraRuntime, LMSpec
from repro.launch.serve import make_params


def main():
    # ONE runtime instance hosts every function (the paper's density story)
    rt = HydraRuntime(memory_budget_bytes=4 << 30)

    # 1. register a couple of classic serverless functions
    specs = catalog()
    rt.register_function("tenantA/hash", specs["jv/filehashing"], tenant="A")
    rt.register_function("tenantB/thumb", specs["py/thumbnail"], tenant="B")

    out = rt.invoke("tenantA/hash", example_args(specs["jv/filehashing"]))
    print("filehashing ->", {k: v.shape if hasattr(v, 'shape') else v
                             for k, v in out.items()})
    out = rt.invoke("tenantB/thumb", example_args(specs["py/thumbnail"]))
    print("thumbnail   ->", out["thumb"].shape)

    # 2. register an LM serving function (an assigned architecture)
    cfg = get_config("qwen2.5-3b").reduced()
    params = make_params(cfg, seed=0)
    rt.register_function("tenantA/lm",
                         LMSpec(cfg=cfg, params=params, max_seq=64, slots=1),
                         tenant="A")
    toks = rt.generate("tenantA/lm", list(range(12)), max_new_tokens=8)
    print("lm generate ->", toks)

    # 3. density accounting: cold vs warm, shared executables, arena pool
    print("\nruntime stats:")
    s = rt.stats()
    print("  functions:", s["functions"])
    print("  exe cache:", s["exe_cache"])
    print("  arenas:   ", s["arena"])
    print("  budget:    %.1f / %.1f MB" % (s["budget_used"] / 2**20,
                                           rt.budget.capacity / 2**20))
    rt.shutdown()


if __name__ == "__main__":
    main()
