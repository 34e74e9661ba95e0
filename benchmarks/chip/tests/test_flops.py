"""Model FLOPs a training token, worked out by hand for the
configurations at their cells' sequence lengths."""
import json
import os

from benchmarks.chip import flops, spec

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _conf(name):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_qwen2_0p5b_l16_at_512():
    # d 896, 14 query and 2 kv heads of 64, d_ff 4864, 16 layers, V 151936
    q_o = 2 * (2 * 896 * 896)                # q and o: 1,605,632 each
    k_v = 2 * (2 * 896 * 128)                # k and v: 229,376 each
    mlp = 3 * (2 * 896 * 4864)               # 26,148,864
    attn = 2 * 2 * 896 * 513 / 2             # scores + values, causal
    head = 2 * 896 * 151936                  # 272,269,312
    assert q_o + k_v + mlp + attn == 30_738_176
    forward = 16 * 30_738_176 + head
    assert forward == 764_080_128
    got = flops.train_flops_per_token(_conf("qwen2-0.5b-l16"), 512)
    assert got == 3 * forward == 2_292_240_384


def test_qwen2_0p5b_l16_at_4096():
    # only the causal attention term grows with the sequence
    attn = 2 * 2 * 896 * 4097 / 2            # 7,341,824
    layer = 30_738_176 - 2 * 2 * 896 * 513 / 2 + attn
    assert layer == 37_160_704
    got = flops.train_flops_per_token(_conf("qwen2-0.5b-l16"), 4096)
    assert got == 3 * (16 * layer + 272_269_312) == 2_600_521_728


def test_mamba2_1p3b_l7_at_4096():
    # d 2048, d_in 4096, 64 heads of 64, state 128, 1 group, chunk 256
    proj = (2 * 2 * 2048 * 4096              # x and z: 33,554,432
            + 2 * 2 * 2048 * 128             # B and C: 1,048,576
            + 2 * 2048 * 64                  # dt: 262,144
            + 2 * 4096 * 2048)               # out: 16,777,216
    assert proj == 51_642_368
    per_head = (2 * 128 * 257 / 2            # C B^T in a chunk, causal
                + 2 * 64 * 257 / 2           # (C B^T . L) x, causal
                + 2 * 128 * 64               # chunk states
                + 2 * 128 * 64               # states into outputs
                + 2 * 128 * 64 / 256)        # state passing
    assert per_head == 82_176
    forward = 7 * (proj + 64 * per_head) + 2 * 2048 * 50277
    assert forward == 604_246_016
    got = flops.train_flops_per_token(_conf("mamba2-1.3b-l7"), 4096)
    assert got == 3 * forward == 1_812_738_048


def test_chunk_is_cut_to_a_short_sequence():
    conf = _conf("mamba2-1.3b-l7")
    forward = spec.model_type("mamba2").forward_flops
    assert forward(conf, 64) == forward(dict(conf, chunk_size=64), 64)

