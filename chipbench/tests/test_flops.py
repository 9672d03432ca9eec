"""Model FLOPs per token against a count by hand (PERF.md, section 3)."""
import json

from chipbench import flops, spec


def conf(name):
    return json.loads((spec.HERE / "configs" / f"{name}.json").read_text())


def test_starcoder2_by_hand():
    # per layer: wq, wo 4608 x 4608; wk, wv 4608 x 512; up, down 4608 x 18432
    layer = 2 * 4608 * 4608 + 2 * 4608 * 512 + 2 * 4608 * 18432
    unembed = 49152 * 4608
    assert flops.matmul_params(conf("starcoder2-7b-chip")) \
        == layer + unembed == 443547648
    # causal, window 4096 = seq: a query sees (4096 + 1) / 2 keys on average
    attn = 12 * 4608 * 2048.5
    assert flops.per_token(conf("starcoder2-7b-chip"), 4096) \
        == 6 * 443547648 + attn == 2774559744


def test_rwkv6_by_hand():
    d, f = 2048, 7168
    layer = (5 * d * d + d * 160 + 5 * 32 * d + d * 64 + 64 * d
             + 2 * d * f + d * d)
    assert layer == 55443456
    assert flops.matmul_params(conf("rwkv6-1.6b-chip")) \
        == 6 * layer + 65536 * d == 466878464
    assert flops.per_token(conf("rwkv6-1.6b-chip"), 4096) \
        == 6 * 466878464 + 12 * 6 * d * 64 == 2810707968


def test_mean_context():
    from chipbench.families import starcoder2
    assert starcoder2.mean_context(4, None) == 2.5
    assert starcoder2.mean_context(4, 2) == 1.75      # 1, 2, 2, 2
