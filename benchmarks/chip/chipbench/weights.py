"""Weights from a seed, made by the benchmark and not by the program.

A configuration's reference module gives the layout: each leaf's path,
shape, whether it is stacked over layers, and its init. Every value is
the top byte of an integer hash (lowbias32, two rounds) of the element's
index and two words drawn from a key folded from the seed, the tenant,
the leaf's path and (for stacked leaves) the layer; it is then moved and
scaled by one floating-point operation each and rounded once to
bfloat16. Integer hashing is exact and cheap, so the program's whole
tree, made in one jitted call on the device, and the reference's one
layer, made alone after the window, hold the same values bit for bit.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

BYTE_STD = math.sqrt((256 ** 2 - 1) / 12.0)   # std of a uniform byte


def seed_words(seed: int, tenant: int) -> np.ndarray:
    """A seed of any size (seeds may exceed 32 bits) and a tenant
    index, as three uint32 words."""
    if seed < 0:
        raise ValueError(f"seed {seed} is negative")
    return np.array([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF, tenant],
                    np.uint32)


def _key(words, path: str):
    k = jax.random.PRNGKey(0)
    for w in (words[0], words[1], words[2]):
        k = jax.random.fold_in(k, w)
    return jax.random.fold_in(k, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def _mix(x):
    """lowbias32: an invertible integer hash of uint32 words."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def _bytes(key, shape) -> jax.Array:
    """One byte (0..255, as float32) per element."""
    s = jax.random.bits(key, (2,), jnp.uint32)
    idx = jax.lax.iota(jnp.uint32, math.prod(shape)).reshape(shape)
    h = _mix(_mix(idx ^ s[0]) + s[1])
    return (h >> 24).astype(jnp.float32)


def _values(key, shape, init: dict) -> jax.Array:
    kind = init["kind"]
    if kind == "const":
        return jnp.full(shape, init["value"], jnp.float32)
    u = _bytes(key, shape)
    if kind == "sym":              # zero mean, std as given
        return (u - 127.5) * jnp.float32(init["std"] / BYTE_STD)
    if kind == "range":            # evenly over [lo, hi]
        s = (init["hi"] - init["lo"]) / 255.0
        return (u + jnp.float32(init["lo"] / s)) * jnp.float32(s)
    raise ValueError(f"unknown init {kind!r}")


def leaf(words, path: str, spec: dict, layer=None) -> jax.Array:
    """One leaf (or one layer of a stacked leaf) as bfloat16."""
    k = _key(words, path)
    shape = tuple(spec["shape"])
    if spec.get("stacked"):
        shape = shape[1:]
        k = jax.random.fold_in(k, layer)
    return _values(k, shape, spec["init"]).astype(jnp.bfloat16)


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, v in flat.items():
        node = tree
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return tree


def make_tree(layout: dict):
    """A jitted function (uint32[3] seed words) -> the whole bf16 tree,
    nested by path: one call per tenant, one compile per layout."""
    def build(words):
        flat = {}
        for path, spec in layout.items():
            if spec.get("stacked"):
                # layer by layer inside the one program: the random bits
                # of one layer are live at a time, not of the whole stack
                L = spec["shape"][0]
                flat[path] = jax.lax.map(
                    lambda l, p=path, s=spec: leaf(words, p, s, l),
                    jnp.arange(L, dtype=jnp.uint32))
            else:
                flat[path] = leaf(words, path, spec)
        return _nest(flat)
    return jax.jit(build)


def layer_fn(layout: dict):
    """A jitted function (seed words, layer) -> {path: float32} of every
    stacked leaf's slice at that layer."""
    stacked = {p: s for p, s in layout.items() if s.get("stacked")}

    def one(words, layer):
        return {p: leaf(words, p, s, layer).astype(jnp.float32)
                for p, s in stacked.items()}
    return jax.jit(one)


def flat_fn(layout: dict):
    """A jitted function (seed words, path) -> that leaf, not stacked, as
    float32."""
    def one(words, path):
        return leaf(words, path, layout[path]).astype(jnp.float32)
    return jax.jit(one, static_argnums=1)


def check_layout(layout: dict, program_shapes) -> None:
    """Raise unless the program's parameter tree has exactly the
    layout's paths and shapes."""
    flat = {}

    def walk(node, prefix):
        for k, v in node.items():
            p = f"{prefix}/{k}" if prefix else k
            if isinstance(v, dict):
                walk(v, p)
            else:
                flat[p] = tuple(v.shape)
    walk(program_shapes, "")
    want = {p: tuple(s["shape"]) for p, s in layout.items()}
    if flat != want:
        missing = sorted(set(want) - set(flat))
        extra = sorted(set(flat) - set(want))
        wrong = sorted(p for p in set(want) & set(flat)
                       if want[p] != flat[p])
        raise ValueError(f"the program's parameters differ from the "
                         f"reference layout: missing {missing}, extra "
                         f"{extra}, other shapes {wrong}")
