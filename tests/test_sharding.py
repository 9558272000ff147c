"""Sharding policy rules + a real multi-device lower/compile smoke (run in a
subprocess so the 8-device XLA flag doesn't contaminate this process)."""
import json
import subprocess
import sys
import textwrap

import jax
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.launch.sharding import (default_rules, logical_spec,
                                   param_specs, use_rules)
from repro.models import transformer as tf


def mesh1():
    return make_mesh((1, 1), ("data", "model"))


def test_param_rules_no_duplicate_axes():
    """No PartitionSpec may map one mesh axis to two dims (for every arch
    and both serve/train rule-sets)."""
    m = mesh1()
    for arch in ("qwen2.5-3b", "dbrx-132b", "granite-moe-1b-a400m",
                 "mamba2-780m", "zamba2-2.7b", "gemma3-1b"):
        cfg = get_config(arch).reduced()
        params = jax.eval_shape(
            lambda c=cfg: tf.init_params(jax.random.PRNGKey(0), c))
        for fsdp in (False, True):
            rules = default_rules(m, fsdp=fsdp)
            specs = param_specs(params, rules, cfg)
            for s in jax.tree.leaves(
                    specs, is_leaf=lambda x: isinstance(x, P)):
                flat = [a for dim in s for a in
                        (dim if isinstance(dim, tuple) else (dim,))
                        if a is not None]
                assert len(flat) == len(set(flat)), (arch, s)


def test_kv_replicated_when_heads_not_divisible():
    """gemma3 has 1 KV head: its wk/wv must be replicated under TP-16
    (production mesh geometry via AbstractMesh — no devices needed)."""
    m = jax.sharding.AbstractMesh((16, 16), ("data", "model"))
    cfg = get_config("gemma3-1b")
    params = jax.eval_shape(
        lambda: tf.init_params(jax.random.PRNGKey(0), cfg))
    rules = default_rules(m)
    specs = param_specs(params, rules, cfg)
    wk = specs["layers"]["attn"]["wk"]
    assert all(a is None for a in wk), wk
    wq = specs["layers"]["attn"]["wq"]
    assert "model" in [a for a in wq if a]


def test_logical_spec_resolution():
    m = mesh1()
    rules = default_rules(m, fsdp=True, kv_seq=True)
    with use_rules(rules):
        assert logical_spec("batch", None, "ff") == P(None, None, "model")
        # kv_seq claims data; batch excludes it
        assert rules.kv_seq == "data"
        assert "data" not in rules.batch


def test_no_rules_is_noop(rng):
    from repro.launch.sharding import shard
    x = jax.numpy.ones((4, 4))
    assert shard(x, "batch", None) is x


SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, dataclasses
    import jax, jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from repro.launch.sharding import default_rules, named_sharding_tree, use_rules
    from repro.launch.roofline import analyze
    from repro.models.programs import ModelProgram
    from repro.configs import get_config
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = make_mesh((4, 2), ("data", "model"))
    cfg = get_config("qwen2.5-3b").reduced()
    prog = ModelProgram(cfg, remat=False, unroll=True)
    rules = default_rules(mesh, fsdp=True)
    with use_rules(rules):
        params = jax.eval_shape(lambda: prog.init(jax.random.PRNGKey(0)))
        pspecs = named_sharding_tree(params, rules, cfg)
        batch = {"tokens": jax.ShapeDtypeStruct((8, 32), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((8, 32), jnp.int32)}
        bspecs = {k: NamedSharding(mesh, P("data", None)) for k in batch}
        def loss(p, b):
            return prog.loss_fn(p, b)[0]
        comp = jax.jit(jax.grad(loss), in_shardings=(pspecs, bspecs)).lower(
            params, batch).compile()
        r = analyze(comp, mesh.size)
        print(json.dumps({"flops": r.flops_per_device,
                          "wire": r.wire_bytes_per_device,
                          "ncoll": r.collectives["count"]}))
""")


def test_multi_device_lower_compile_and_collectives():
    """Real SPMD compile on 8 host devices: collectives must appear and the
    roofline analyzer must parse them."""
    out = subprocess.run(
        [sys.executable, "-c", SUBPROC], capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"}, cwd="/root/repo",
        timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    stats = json.loads(out.stdout.strip().splitlines()[-1])
    assert stats["flops"] > 0
    assert stats["ncoll"] > 0          # FSDP+TP must emit collectives
    assert stats["wire"] > 0
