"""The FLOP and byte functions against hand counts."""
import json
import os

from chipbench import flops, peaks

HERE = os.path.dirname(os.path.abspath(__file__))


def cfg(name):
    with open(os.path.join(HERE, "..", "configs", name + ".json")) as fh:
        return json.load(fh)


def test_mistral_counts():
    c = cfg("mistral_7b_l4")
    # q 4096x4096, k and v 4096x1024, o 4096x4096, three 4096x14336
    layer = 16777216 + 2 * 4194304 + 16777216 + 3 * 58720256
    assert flops.layer_matmul_params(c) == layer == 218103808
    assert flops.matmul_params(c) == 4 * layer + 4096 * 32000 == 1003487232
    assert flops.all_params(c) == 1003487232 + 4096 * 32000 + 8 * 4096 + 4096
    # per token of a 4096-token sequence: 6 x matmul parameters plus
    # 3 x 4 layers x (4 x 32 x 128) x 4097 / 2 attention pairs
    per_step = flops.train_flops_per_step(c, 3, 4096)
    per_token = per_step / (3 * 4096)
    assert per_token == 6 * 1003487232 + 3 * 4 * 16384 * 4097 / 2
    assert 6.41e9 < per_token < 6.43e9


def test_deepseek_counts():
    c = cfg("deepseek_llm_7b_l8")
    layer = 4 * 16777216 + 3 * 4096 * 11008
    assert flops.layer_matmul_params(c) == layer == 202375168
    assert flops.matmul_params(c) == 8 * layer + 4096 * 102400
    assert flops.all_params(c) == 8 * (layer + 8192) + 2 * 4096 * 102400 + 4096
    # one decoded token at 1000 cached positions: body and head once,
    # 8 layers x 4 x 32 x 128 x 1000 for attention
    one = flops.forward_flops(c, 1, 1000)
    assert one == 2 * (8 * layer + 4096 * 102400) + 8 * 16384 * 1000
    # a 512-token prefill needs the head for its last row only
    pre = flops.forward_flops(c, 512, flops.causal_pairs(512), head_tokens=1)
    assert pre == 2 * 8 * layer * 512 + 2 * 4096 * 102400 \
        + 8 * 16384 * (512 * 513 // 2)


def test_flash_call():
    c = cfg("mistral_7b_l4")
    work, moved = flops.flash_attention_call(c, 3, 4096)
    pairs = 3 * 4096 * 4097 // 2
    assert work == 3 * 4 * 32 * 128 * pairs
    q = 3 * 4096 * 32 * 128 * 2
    kv = 3 * 4096 * 8 * 128 * 2
    assert moved == 6 * q + 6 * kv
    peak = peaks.peaks_for("TPU v5 lite")
    # bound by compute: 1.237 TFLOP at 197 TFLOP/s
    assert abs(peaks.least_seconds(work, moved, peak) - work / 197e12) < 1e-12
    assert 6.2e-3 < work / 197e12 < 6.4e-3


def test_paged_call():
    c = cfg("deepseek_llm_7b_l8")
    work, moved = flops.paged_attention_calls(c, rows=32, context_tokens=21000)
    assert work == 4 * 32 * 128 * 21000
    assert moved == 2 * 32 * 128 * 2 * 21000 + 2 * 32 * 128 * 2 * 32
    peak = peaks.peaks_for("TPU v5 lite")
    # bound by memory: 344 MB at 819 GB/s
    assert peaks.least_seconds(work, moved, peak) == moved / 819e9


def test_unknown_device_is_an_error():
    import pytest
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9")
