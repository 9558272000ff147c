"""ROADMAP "cross-PROCESS restore": a snapshot taken by one Python
process restores in a FRESH process with zero recompiles.

The in-process variant (test_platform.py) already proves a freshly
*constructed* platform restores through the persisted ExecutableCache;
this harness proves it across a real process boundary — the restart
story the paper's Native-Image-binary-on-disk analog promises. The
parent registers + snapshots + exports a function and shuts down; a
subprocess with its own interpreter (fresh JAX, fresh caches) imports
the exported record, restores from the on-disk snapshot, serves the
function, and reports its executable-cache stats: ``compiles`` must be
0 and ``disk_hits`` >= 1."""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

from repro.core import CallableSpec, HydraPlatform
from repro.core.executable_cache import DEFAULT_COMPILE_CACHE_DIR

MB = 1 << 20
REPO_SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")

# the child rebuilds the SAME spec (program name + shapes = the
# executable-cache key; weights come from the snapshot, not from here)
CHILD_SCRIPT = r"""
import json, sys
import jax
import jax.numpy as jnp
from repro.core import CallableSpec, HydraPlatform
from repro.core.executable_cache import configure_compile_cache

configure_compile_cache()
meta = json.load(open(sys.argv[1]))

def fn(params, args):
    return {"y": args["x"] * params["w"] + 1.0}

spec = CallableSpec(name="xproc", fn=fn,
                    example_args={"x": jnp.ones((64,), jnp.float32)},
                    params=None, arena_bytes=1 << 20)
plat = HydraPlatform(pool_size=1, runtime_budget_bytes=64 << 20,
                     snapshot_dir=meta["snapshot_dir"])
try:
    plat.import_function({
        "fid": meta["fid"], "spec": spec, "tenant": meta["tenant"],
        "mem_budget": meta["mem_budget"], "need_bytes": meta["need_bytes"],
        "params_spec": {"w": jax.ShapeDtypeStruct((64,), jnp.float32)},
        "invocations": meta["invocations"],
        "snapshot_path": meta["snapshot_path"]})
    plat.restore(meta["fid"])
    out = plat.invoke(meta["fid"], {"x": jnp.full((64,), 3.0)})
    print(json.dumps({"y0": float(out["y"][0]),
                      **plat.exe_cache.stats()}))
finally:
    plat.shutdown()
"""


def test_restore_in_fresh_process_zero_recompiles(tmp_path):
    def fn(params, args):
        return {"y": args["x"] * params["w"] + 1.0}

    spec = CallableSpec(name="xproc", fn=fn,
                        example_args={"x": jnp.ones((64,), jnp.float32)},
                        params={"w": jnp.full((64,), 2.0)},
                        arena_bytes=1 * MB)
    plat = HydraPlatform(pool_size=1, runtime_budget_bytes=64 * MB,
                         snapshot_dir=str(tmp_path))
    try:
        plat.register_function("t0/f", spec, tenant="t0")
        before = plat.invoke("t0/f", {"x": jnp.full((64,), 3.0)})
        exported = plat.export_function("t0/f")
    finally:
        plat.shutdown()
    # program + its arena-signature zeroer: both compiled at registration
    assert plat.exe_cache.stats()["compiles"] == 2

    meta = {"snapshot_dir": str(tmp_path),
            "fid": exported["fid"], "tenant": exported["tenant"],
            "mem_budget": exported["mem_budget"],
            "need_bytes": exported["need_bytes"],
            "invocations": exported["invocations"],
            "snapshot_path": exported["snapshot_path"]}
    meta_path = tmp_path / "export.json"
    meta_path.write_text(json.dumps(meta))
    child = tmp_path / "child.py"
    child.write_text(CHILD_SCRIPT)

    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "xla")
    proc = subprocess.run([sys.executable, str(child), str(meta_path)],
                          capture_output=True, text=True, timeout=300,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    stats = json.loads(proc.stdout.strip().splitlines()[-1])

    # the fresh process served the restored function correctly...
    assert stats["y0"] == float(before["y"][0]) == 7.0
    # ...with ZERO compilations: the executable deserialized from the
    # cache persisted by the PARENT process
    assert stats["compiles"] == 0
    assert stats["disk_hits"] >= 1
    # jax's persistent compilation cache comes from the environment, as
    # the child's entry point configured it: never from snapshot_dir
    assert stats["xla_cache_dir"] == str(tmp_path / "xla")
    assert not (tmp_path / "xla_cache").exists()


# ---------------------------------------------------------------------------
XLA_CACHE_CHILD = r"""
import jax
import jax.numpy as jnp
from repro.core.executable_cache import configure_compile_cache

cache_dir = configure_compile_cache()
out = jax.jit(lambda x: (x * 3.0 + 1.0).sum())(jnp.ones((257,), jnp.float32))
print(cache_dir)
print(float(out))
"""


@pytest.mark.parametrize("source", ["env", "default"])
def test_xla_persistent_cache_reused_by_fresh_process(tmp_path, source):
    """The layer UNDER our serialize_executable payloads: jax's persistent
    compilation cache, configured by an entry point. Its directory is
    ``JAX_COMPILATION_CACHE_DIR`` when set, else one fixed path in the
    checkout. The first process writes its XLA compilation there; a
    second, fresh process compiling the same program replays it from
    disk — no new cache entries appear. (Subprocesses: the cache is
    process-global.)"""
    script = tmp_path / "xla_child.py"
    script.write_text(XLA_CACHE_CHILD)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    if source == "env":
        cache_dir = str(tmp_path / "xla")
        env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    else:
        cache_dir = DEFAULT_COMPILE_CACHE_DIR
        env.pop("JAX_COMPILATION_CACHE_DIR", None)

    def run_once():
        proc = subprocess.run(
            [sys.executable, str(script)],
            capture_output=True, text=True, timeout=300, env=env)
        assert proc.returncode == 0, proc.stderr[-2000:]
        lines = proc.stdout.strip().splitlines()
        assert lines[-2:] == [cache_dir, "1028.0"]
        return sorted(os.listdir(cache_dir))

    first = run_once()
    assert first                     # the compile was written to disk
    second = run_once()
    assert second == first           # cache hit: nothing new written
