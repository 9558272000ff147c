"""Per-architecture smoke tests (reduced configs) + cache-path consistency.

Every assigned arch: one forward + one train step on CPU, asserting output
shapes and no NaNs; prefill+decode must match the cacheless forward.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import applicable_shapes, get_config, list_archs
from repro.models import transformer as tf
from repro.models.programs import ModelProgram
from repro.optim import AdamW, constant

ARCHS = list_archs()


def make_batch(cfg, B, S, rng):
    batch = {}
    if cfg.family == "audio":
        batch["embeds"] = jax.random.normal(
            rng, (B, S, cfg.d_model), jnp.float32).astype(
            jnp.dtype(cfg.dtype))
    elif cfg.family == "vlm":
        ft = cfg.frontend_tokens
        batch["embeds"] = jax.random.normal(
            rng, (B, ft, cfg.d_model)).astype(jnp.dtype(cfg.dtype))
        batch["tokens"] = jax.random.randint(rng, (B, S - ft), 0,
                                             cfg.vocab_size)
    else:
        batch["tokens"] = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)
    batch["labels"] = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)
    return batch


def split_last(cfg, full):
    """(prefill inputs, decode inputs): all but the last position, and
    the last (a VLM's image embeddings all go to the prefill)."""
    key = "embeds" if cfg.family == "audio" else "tokens"
    pre = dict(full)
    pre[key] = full[key][:, :-1]
    return pre, {key: full[key][:, -1:]}


def grow_kv(cache, n):
    """``cache`` with ``n`` empty positions appended to its KV slabs, so
    decode has room."""
    cache = dict(cache)
    for key in ("k", "v"):
        if key in cache:
            kv = cache[key]
            cache[key] = jnp.concatenate(
                [kv, jnp.zeros(kv.shape[:2] + (n,) + kv.shape[3:],
                               kv.dtype)], axis=2)
    return cache


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_and_train_step(arch, rng):
    cfg = get_config(arch).reduced()
    prog = ModelProgram(cfg, remat=True)
    params = prog.init(rng)
    B, S = 2, 32
    batch = make_batch(cfg, B, S, rng)

    logits, aux = jax.jit(
        lambda p, b: tf.forward(p, cfg, tokens=b.get("tokens"),
                                embeds=b.get("embeds")))(params, batch)
    assert logits.shape == (B, S, cfg.vocab_size), logits.shape
    assert not bool(jnp.any(jnp.isnan(logits.astype(jnp.float32))))

    opt = AdamW(lr=constant(1e-3))
    step = jax.jit(prog.make_train_step(opt, n_micro=2))
    params2, _, mets = step(params, opt.init(params), batch)
    assert np.isfinite(float(mets["loss"]))
    # params changed
    delta = jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))), params, params2))
    assert max(delta) > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_prefill_decode_matches_forward(arch, rng):
    cfg = dataclasses.replace(get_config(arch).reduced(), dtype="float32")
    prog = ModelProgram(cfg, remat=False)
    params = prog.init(rng)
    B, S = 2, 16
    full = make_batch(cfg, B, S + 1, rng)
    full.pop("labels")

    logits_full, _ = jax.jit(
        lambda p, b: tf.forward(p, cfg, tokens=b.get("tokens"),
                                embeds=b.get("embeds")))(params, full)

    pre, dec_in = split_last(cfg, full)
    last_logits, cache = jax.jit(prog.prefill)(params, pre)
    np.testing.assert_allclose(last_logits, logits_full[:, S - 1],
                               atol=3e-5, rtol=3e-5)
    cache = grow_kv(cache, 4)
    dec_logits, new_cache = jax.jit(prog.decode_step)(params, cache, dec_in)
    np.testing.assert_allclose(dec_logits, logits_full[:, -1],
                               atol=3e-5, rtol=3e-5)
    assert int(new_cache["length"][0]) == S + 1


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_free_row_stays_at_length_zero(arch, rng):
    """A row at length 0 (a free serving slot) decodes to finite logits
    and stays at 0; the live row beside it advances by one and still
    matches the cacheless forward."""
    cfg = dataclasses.replace(get_config(arch).reduced(), dtype="float32")
    prog = ModelProgram(cfg, remat=False)
    params = prog.init(rng)
    B, S = 2, 16
    full = make_batch(cfg, B, S + 1, rng)
    full.pop("labels")
    logits_full, _ = jax.jit(
        lambda p, b: tf.forward(p, cfg, tokens=b.get("tokens"),
                                embeds=b.get("embeds")))(params, full)
    pre, dec_in = split_last(cfg, full)
    _, cache = jax.jit(prog.prefill)(params, pre)
    cache = grow_kv(cache, 4)
    cache["length"] = cache["length"].at[1].set(0)
    dec_logits, new_cache = jax.jit(prog.decode_step)(params, cache, dec_in)
    assert new_cache["length"].tolist() == [S + 1, 0]
    assert np.isfinite(np.asarray(dec_logits)).all()
    np.testing.assert_allclose(dec_logits[0], logits_full[0, -1],
                               atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("arch", ARCHS)
def test_input_specs_cover_all_shapes(arch):
    cfg = get_config(arch)
    prog = ModelProgram(cfg)
    for shape in applicable_shapes(cfg):
        specs = prog.input_specs(shape)
        assert specs, (arch, shape.name)
        for v in jax.tree.leaves(specs):
            assert isinstance(v, jax.ShapeDtypeStruct)
        if shape.kind == "decode":
            cache = prog.cache_specs(shape.global_batch, shape.seq_len)
            assert prog.cache_bytes(shape.global_batch, shape.seq_len) > 0
            assert "length" in cache


def test_gemma_window_pattern():
    cfg = get_config("gemma3-1b")
    w = np.asarray(tf.layer_windows(cfg))
    assert (w[:5] == cfg.sliding_window).all()
    assert w[5] > 1e8          # every 6th layer is global
    assert (w != cfg.sliding_window).sum() == cfg.n_layers // 6


def test_unroll_equals_scan(rng):
    for arch in ("gemma3-1b", "zamba2-2.7b", "mamba2-780m"):
        cfg = dataclasses.replace(get_config(arch).reduced(),
                                  dtype="float32")
        params = tf.init_params(rng, cfg)
        toks = jax.random.randint(rng, (2, 16), 0, cfg.vocab_size)
        a, _ = jax.jit(lambda p, t: tf.forward(p, cfg, tokens=t))(params,
                                                                  toks)
        b, _ = jax.jit(lambda p, t: tf.forward(p, cfg, tokens=t,
                                               unroll=True))(params, toks)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-5, rtol=2e-5)
