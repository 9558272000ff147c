"""Operation and byte counts against hand counts at qwen2.5-3b and
mamba2-780m widths, and the kernels recognised in HLO text."""
import json
from pathlib import Path

import pytest

from chipbench import costs, kernels, spec

BENCH = Path(__file__).resolve().parent
CONFIGS = BENCH / "configs"
PEAK = spec.peaks()["TPU v5 lite"]


def _cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_decode_attention_counts():
    # qwen2.5-3b: 16 query heads, 2 KV heads of 128; slots at 1000, 500
    flops, nbytes = costs.decode_attention([1000, 500], 16, 2, 128)
    assert flops == 4 * 16 * 128 * 1500
    assert nbytes == (2 * 1500 * 2 * 128 + 2 * 2 * 16 * 128) * 2
    t, bound = costs.least_time_s(flops, nbytes, PEAK)
    assert bound == "memory" and t == pytest.approx(nbytes / 819e9)


def test_flash_attention_counts():
    flops, nbytes = costs.flash_attention(256, 16, 2, 128)
    assert flops == 4 * 16 * 128 * (256 * 257 // 2)
    assert nbytes == (32 + 4) * 256 * 128 * 2
    # at 256 tokens the bytes bound it, at 1024 the operations
    assert costs.least_time_s(flops, nbytes, PEAK)[1] == "memory"
    assert costs.least_time_s(*costs.flash_attention(1024, 16, 2, 128),
                              PEAK)[1] == "compute"


def test_ssd_scan_counts():
    # mamba2-780m: 48 heads of 64, state 128, one chunk of 256
    flops, nbytes = costs.ssd_scan(256, 48, 64, 128, 256)
    tri = 256 * 257 // 2
    assert flops == 2 * tri * 128 + 48 * (2 * tri * 64 + 4 * 256 * 128 * 64)
    assert flops == 613_187_584
    assert nbytes == (2 * 256 * 48 * 64 * 2 + 256 * 48 * 4
                      + 2 * 256 * 128 * 2 + 48 * 4 + 2 * 48 * 64 * 128 * 4)
    # two chunks of 128 hold fewer causal pairs than one of 256
    assert costs.ssd_scan(256, 48, 64, 128, 128)[0] < flops


def _reference(model):
    return spec.load_module(spec.BENCH_DIR / "reference" / f"{model}.py")


def test_qwen_step_flops():
    ref, cfg = _reference("qwen2"), _cfg("qwen2.5-3b.x2")
    per_token = 2 * 36 * (2048 * 20 * 128 + 2048 * 2048 + 3 * 2048 * 11008)
    assert per_token == 5_549_064_192      # 2 x 2.77e9 non-embedding
    head = 2 * 151936 * 2048
    attn = 4 * 36 * 16 * 128
    assert ref.step_flops(cfg, "decode", [100, 200]) == pytest.approx(
        2 * (per_token + head) + attn * 300)
    assert ref.step_flops(cfg, "prefill", [512]) == pytest.approx(
        512 * per_token + head + attn * 512 * 513 / 2)


def test_mamba_step_flops():
    ref, cfg = _reference("mamba2"), _cfg("mamba2-780m.x6")
    d = ref.dims(cfg)
    assert (d["din"], d["H"], d["N"], d["P"], d["V"]) == (
        3072, 48, 128, 64, 50288)
    in_dim = 2 * 3072 + 2 * 128 + 48
    per_token = 48 * (2 * 1536 * in_dim + 2 * 3072 * 1536
                      + 2 * 4 * (3072 + 256))
    head = 2 * 50288 * 1536
    ssm = 48 * 5 * 48 * 64 * 128
    assert ref.step_flops(cfg, "decode", [7, 9, 11]) == pytest.approx(
        3 * (per_token + head + ssm))
    scan = costs.ssd_scan(128, 48, 64, 128, 256)[0]
    assert ref.step_flops(cfg, "prefill", [128]) == pytest.approx(
        128 * per_token + head + 48 * scan)


# operations as the TPU trace records them (qwen2.5-3b and mamba2-780m)
RMS = ('%closed_call.10 = bf16[8,1536]{1,0:T(8,128)(2,1)S(1)} custom-call('
       'bf16[8,1536]{1,0:T(8,128)(2,1)S(1)} %bitcast.102, bf16[1536]{0:T('
       '1024)(128)(2,1)S(1)} %dynamic-slice_reduce_fusion.4), custom_call_'
       'target="tpu_custom_call", operand_layout_constraints={bf16[8,1536]'
       '{1,0}, bf16[1536]{0}}, frontend_attributes={kernel_metadata={}}')
SSD = ('%closed_call.22 = (bf16[1,48,128,64]{3,2,1,0:T(8,128)(2,1)S(1)}, f3'
       '2[1,48,64,128]{3,2,1,0:T(8,128)S(1)}) custom-call(bf16[1,48,128,64]'
       '{3,2,1,0:T(8,128)(2,1)S(1)} %bitcast.97, f32[1,48,1,128]{3,2,1,0:T('
       '1,128)S(1)} %copy.44, f32[1,48,128,1]{3,2,1,0:T(8,128)S(1)} %copy.'
       '45, f32[48]{0:T(128)S(1)} %fusion.67, bf16[1,128,128]{2,1,0:T(8,12'
       '8)(2,1)S(1)} %get-tuple-element.256, bf16[1,128,128]{2,1,0:T(8,128'
       ')(2,1)S(1)} %get-tuple-element.257, f32[1,48,64,128]{3,2,1,0:T(8,1'
       '28)S(1)} %broadcast_in_dim.46), custom_call_target="tpu_custom_cal'
       'l", operand_layout_constraints={bf16[1,48,128,64]{3,2,1,0}}')
DEC = ('%closed_call.23 = bf16[16,16,128]{2,1,0:T(8,128)(2,1)S(1)} custom-'
       'call(s32[16]{0:T(128)S(1)} %broadcast_add_fusion.3, s32[1]{0:T(128)'
       '} %get-tuple-element.602, bf16[16,16,128]{2,1,0:T(8,128)(2,1)S(1)} '
       '%fusion.9, bf16[16,1536,2,128]{3,2,1,0:T(2,128)(2,1)S(1)} %gte.1, b'
       'f16[16,1536,2,128]{3,2,1,0:T(2,128)(2,1)S(1)} %gte.2), custom_call_'
       'target="tpu_custom_call", frontend_attributes={kernel_metadata={}}')
FLASH = ('%closed_call.5 = bf16[1,16,256,128]{3,2,1,0} custom-call(s32[1]{0'
         ':T(128)} %c.1, bf16[1,16,256,128]{3,2,1,0} %q, bf16[1,2,256,128]{'
         '3,2,1,0} %k, bf16[1,2,256,128]{3,2,1,0} %v), custom_call_target="'
         'tpu_custom_call", frontend_attributes={kernel_metadata={}}')
FUSION = ('%fusion.131 = s32[4]{0:T(128)S(1)} fusion(s32[16,4]{1,0:T(8,128)'
          'S(1)} %get-tuple-element.521), kind=kLoop, calls=%fused.102')


def test_kernels_recognised():
    assert kernels.kernel_of(RMS) == "rmsnorm"
    assert kernels.kernel_of(SSD) == "ssd_scan"
    assert kernels.kernel_of(DEC) == "decode_attention"
    assert kernels.kernel_of(FLASH) == "flash_attention"
    assert kernels.kernel_of(FUSION) is None
    assert kernels.shapes(SSD) == {"B": 1, "H": 48, "S": 128, "P": 64,
                                   "N": 128}
    assert kernels.shapes(DEC) == {"B": 16, "Hq": 16, "hd": 128, "S": 1536,
                                   "Hkv": 2}
    assert kernels.shapes(FLASH) == {"B": 1, "Hq": 16, "S": 256, "hd": 128,
                                     "Hkv": 2}
