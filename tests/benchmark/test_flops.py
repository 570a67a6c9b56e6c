"""``benchmark/flops/*`` against a count made by hand at a tiny shape."""

from benchmark import harness

TINY = dict(encoder_layers=2, encoder_embed_dim=8, encoder_ffn_embed_dim=16,
            encoder_attention_heads=2, vocab_size=10, gaussian_kernels=4)


def test_bert_by_hand():
    f = harness.load_module("flops", "bert_base")
    # one sequence of 4 tokens, all of them masked (mask_prob 1.0):
    # per layer and token: qkv 2*8*24=384, out 2*8*8=128, ffn 2*2*8*16=512
    # per layer and (query, key) pair: scores 2*8 + weighted sum 2*8 = 32
    # head per token: dense 128 + projection 2*8*10=160
    forward = 4 * 2 * (384 + 128 + 512) + 16 * 2 * 32 + 4 * (128 + 160)
    assert forward == 10368
    assert f.train_flops(TINY, sum_n=4, sum_n2=16, mask_prob=1.0) == 3 * forward
    # padding is not counted: two sequences of 2 cost less than one of 4
    assert f.train_flops(TINY, 4, 8, 1.0) < f.train_flops(TINY, 4, 16, 1.0)


def test_bert_base_per_token_at_512():
    f = harness.load_module("flops", "bert_base")
    cfg = harness.load_json(harness.find("configs", "bert_base.json"))
    per_token = f.train_flops(cfg, 512, 512 * 512, 0.15) / 512
    assert 0.55e9 < per_token < 0.62e9  # PERF.md quotes 0.588 GFLOP


def test_unimol_by_hand():
    f = harness.load_module("flops", "unimol")
    # per token: 2 layers * 1024 + head (128 + 160) = 2336
    # per pair: attention 2*32=64, gbf_proj 2*4*4 + 2*4*2 = 48,
    #           pair heads 2 * (2*2*2 + 2*2) = 24
    assert f.forward_per_token(TINY) == 2336
    assert f.forward_per_pair(TINY) == 64 + 48 + 24
    assert f.train_flops(TINY, 3, 9, 0.15) == 3 * (3 * 2336 + 9 * 136)
