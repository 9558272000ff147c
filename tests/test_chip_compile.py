"""The main path's Pallas kernels compile for a TPU v5e at the widths the
chip serves (qwen2.5-3b; gemma3-1b's hd-256 decode; mamba2-780m's SSD
scan). Nothing runs: the TPU compiler, which is installed without a chip,
compiles for a described v5e and refuses what the chip would refuse —
misaligned blocks, VMEM overflows — which interpret-mode tests cannot see.

The topology is described inside a fixture, never at import time: only
one process may load the TPU library, and every test worker imports this
file."""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssd_scan import ssd_scan

BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep these compiles out of it
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("S", [13, 1024])
def test_flash_attention_qwen_widths(one_chip, S):
    text = compiled_text(functools.partial(flash_attention, causal=True),
                         one_chip, ((1, S, 16, 128), BF16),
                         ((1, S, 2, 128), BF16), ((1, S, 2, 128), BF16))
    assert "tpu_custom_call" in text


def test_flash_attention_traced_window(one_chip):
    """gemma3's per-layer window, traced under the layer scan, is a
    runtime operand of the kernel."""
    text = compiled_text(
        lambda q, k, v, w: flash_attention(q, k, v, causal=True, window=w),
        one_chip, ((1, 1024, 4, 256), BF16), ((1, 1024, 1, 256), BF16),
        ((1, 1024, 1, 256), BF16), ((), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("Hq,Hkv,hd,window", [
    (16, 2, 128, False),     # qwen2.5-3b
    (4, 1, 256, True),       # gemma3-1b: hd 256 needs the 128-key block
])
def test_decode_attention(one_chip, Hq, Hkv, hd, window):
    if window:
        fn = lambda q, k, v, n, w: decode_attention(q, k, v, n, window=w)
        extra = [((), jnp.int32)]
    else:
        fn, extra = decode_attention, []
    text = compiled_text(fn, one_chip, ((8, Hq, hd), BF16),
                         ((8, 1024, Hkv, hd), BF16),
                         ((8, 1024, Hkv, hd), BF16), ((8,), jnp.int32),
                         *extra)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows", [128, 8])
def test_rmsnorm_qwen_width(one_chip, rows):
    text = compiled_text(rmsnorm, one_chip, ((rows, 2048), BF16),
                         ((2048,), jnp.float32))
    assert "tpu_custom_call" in text


def test_ssd_scan_mamba2_widths(one_chip):
    """mamba2-780m: 48 heads of 64, state 128, chunk 256."""
    text = compiled_text(
        functools.partial(ssd_scan, chunk=256), one_chip,
        ((1, 1024, 48, 64), BF16), ((1, 1024, 48), jnp.float32),
        ((48,), jnp.float32), ((1, 1024, 128), BF16),
        ((1, 1024, 128), BF16))
    assert "tpu_custom_call" in text
