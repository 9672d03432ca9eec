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


def test_deepseek2_by_hand():
    import tiny
    c = json.loads((tiny.DATA / "tiny-dsv2.json").read_text())
    # MLA: q_down 128 x 48, q_up 48 x 4 x 24, kv_down 128 x 32, k_rope
    # 128 x 8, k_up and v_up 32 x 4 x 16, wo 4 x 16 x 128
    mla = 128 * 48 + 48 * 4 * 24 + 128 * 32 + 128 * 8 + 2 * 32 * 4 * 16 \
        + 4 * 16 * 128
    assert mla == 28160
    dense = 3 * 128 * 256                      # gate, up, down
    # router 128 x 4, one shared expert of width 64, 4 routed experts of
    # width 64 of which a token uses top_k 2 of the published 4
    moe = 128 * 4 + 3 * 128 * 64 + 4 * 3 * 128 * 64 * 2 // 4
    assert flops.matmul_params(c) == 512 * 128 + 3 * mla + dense + 2 * moe \
        == 396800
    # causal MLA over 64 positions: (64 + 1) / 2 keys, Q.K over 16 + 8,
    # P.V over 16, 4 heads, 3 layers
    attn = 6 * 3 * 4 * (16 + 8 + 16) * 32.5
    assert flops.per_token(c, 64) == 6 * 396800 + attn == 2474400


def test_mean_context():
    from chipbench.families import starcoder2
    assert starcoder2.mean_context(4, None) == 2.5
    assert starcoder2.mean_context(4, 2) == 1.75      # 1, 2, 2, 2
