"""Bytes and operations from shapes, against sums worked by hand."""
import json

import counts
import run


def dims(name):
    conf = json.load(open(run.HERE / "configs" / f"{name}.json"))
    return run.dims_of(conf)


def test_smollm_counts():
    d = dims("smollm-135m")
    # per layer: q 576*576 + k,v 2*576*192 + o 576*576 + gate,up,down
    # 3*576*1536 = 331776 + 221184 + 331776 + 2654208
    assert counts.linear_params(d) == 3_538_944
    # 30 layers at 4 bits = 53,084,160; scales 30 * 5184 * 4 = 622,080;
    # norms 61 * 576 * 4 = 140,544; tied table 49152 * 576 * 2
    assert counts.weight_bytes(d, 4) == (53_084_160 + 622_080 + 140_544
                                         + 56_623_104)
    # 30 layers * (K and V) * 3 heads * (64 int8 + one f32 scale)
    assert counts.kv_bytes_per_position(d) == 12_240
    assert counts.token_ops(d) == 212_336_640
    assert counts.logits_ops(d) == 56_623_104
    # 30 layers * 4 * 9 heads * 64 per key
    assert counts.attention_ops(d, 10) == 691_200
    assert counts.decode_step_bytes(d, 4, 2, 100) == (
        110_469_888 + 2 * 576 * 2 + 100 * 12_240)
    assert counts.prefill_ops(d, 3) == (3 * 212_336_640 + 6 * 69_120
                                        + 56_623_104)


def test_chatglm_counts():
    d = dims("chatglm3-6b")
    # q, o 4096*4096 each; k, v 4096*256 each; 3 * 4096 * 13696
    assert counts.linear_params(d) == 203_948_032
    # 28 layers at 4 bits; scales 28 * 40192 * 4; norms 57 * 4096 * 4;
    # the untied output table 65024 * 4096 * 2 (the embedding is read
    # only row by row)
    assert counts.weight_bytes(d, 4) == (2_855_272_448 + 4_501_504
                                         + 933_888 + 532_676_608)
    # 28 layers * 2 * 2 heads * (128 + 4)
    assert counts.kv_bytes_per_position(d) == 14_784
    assert counts.token_ops(d) == 11_421_089_792
    assert counts.decode_step_ops(d, 1, 1) == (
        11_421_089_792 + 2 * 65024 * 4096 + 28 * 4 * 32 * 128)
