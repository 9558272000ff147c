"""The plain references: weights made alone equal the program's tree bit
for bit; the reference computes what the program computes; and the
float8 control fails the comparison that sound runs pass."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, serving, spec, traffic, weights
from chipbench_testkit import make_root, tiny_config

MODELS = ["qwen2", "mamba2"]
SEED = 2 ** 31 + 4242


def _ref(model):
    return spec.load_module(spec.BENCH_DIR / "reference" / f"{model}.py")


def _program(cfg, dtype="bfloat16"):
    from repro.configs.base import ArchConfig
    from repro.models.programs import ModelProgram
    arch = dataclasses.replace(ArchConfig(**cfg["program"]), dtype=dtype)
    return ModelProgram(arch, remat=False)


@pytest.mark.parametrize("model", MODELS)
def test_weights_alone_equal_the_tree(model):
    cfg = tiny_config(model)
    layout = _ref(model).layout(cfg)
    words = weights.seed_words(SEED, 1)
    tree = weights.make_tree(layout)(words)
    one = weights.layer_fn(layout)
    flat = weights.flat_fn(layout)
    for path, s in layout.items():
        node = tree
        for part in path.split("/"):
            node = node[part]
        assert node.dtype == jnp.bfloat16
        if s.get("stacked"):
            for layer in range(s["shape"][0]):
                got = one(words, jnp.uint32(layer))[path]
                np.testing.assert_array_equal(
                    np.asarray(node[layer], np.float32), np.asarray(got))
        else:
            np.testing.assert_array_equal(
                np.asarray(node, np.float32), np.asarray(flat(words, path)))
    other = weights.make_tree(layout)(weights.seed_words(SEED, 2))
    assert not np.array_equal(np.asarray(other["embed"]["tok"]),
                              np.asarray(tree["embed"]["tok"]))


@pytest.mark.parametrize("model", MODELS)
def test_layout_is_the_programs(model):
    cfg = tiny_config(model)
    prog = _program(cfg)
    shapes = jax.eval_shape(prog.init, jax.random.PRNGKey(0))
    layout = _ref(model).layout(cfg)
    weights.check_layout(layout, shapes)
    bad = dict(layout)
    bad.pop("final_norm")
    with pytest.raises(ValueError, match="missing"):
        weights.check_layout(bad, shapes)


@pytest.mark.parametrize("model", MODELS)
def test_reference_computes_the_program(model):
    """At float32 throughout, the program's forward pass and the
    reference's agree to rounding on the same weights."""
    cfg = tiny_config(model)
    ref = _ref(model)
    layout = ref.layout(cfg)
    words = weights.seed_words(SEED, 0)
    tree = jax.tree.map(lambda x: x.astype(jnp.float32),
                        weights.make_tree(layout)(words))
    prog = _program(cfg, "float32")
    V = ref.dims(cfg)["V"]
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 24), 0,
                                cfg["config"]["vocab_size"])
    from repro.models import transformer
    with jax.default_matmul_precision("highest"):
        logits, _ = jax.jit(lambda p, t: transformer.forward(
            p, prog.cfg, tokens=t))(tree, tokens)
    one = weights.layer_fn(layout)
    flat = weights.flat_fn(layout)
    h = ref.final_hidden(cfg, tokens, lambda l: one(words, jnp.uint32(l)),
                         lambda p: flat(words, p), "f32")
    with jax.default_matmul_precision("highest"):
        got = h @ ref.head(cfg, lambda p: flat(words, p)).T
    assert got.shape == (2, 24, V)
    scale = float(jnp.max(jnp.abs(got)))
    assert float(jnp.max(jnp.abs(got - logits))) < 1e-3 * scale


@pytest.mark.parametrize("model", MODELS)
def test_control_fails_where_sound_runs_pass(model, tmp_path):
    """Served tokens of a tiny cell: the program's widest gap is under the
    configuration's limit, the float8 control's is over it."""
    root, name = make_root(tmp_path, model)
    cell = spec.load_cell(name, root)
    cfg = cell.config
    sched = traffic.generate(cell.traffic, 2.0, SEED, cfg["tenants"],
                             cfg["config"]["vocab_size"])
    dep = serving.Deployment(cell, SEED, lambda m: None)
    try:
        dep.warm(sched.prompt_lengths, lambda m: None)
        win = serving.serve(dep, sched, 2.0)
    finally:
        dep.close()
    got = check.compare(cell, SEED, win.records, ("f32", "fp8"))
    limit = cfg["check"]["gap_limit"]
    assert got["tenants"] == cfg["tenants"]
    assert got["widest"]["f32"] <= limit < got["widest"]["fp8"]
