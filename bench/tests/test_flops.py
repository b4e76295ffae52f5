"""Needed FLOPs per token, against a hand count at the published widths."""
import json
import os

from bench.models import deepseek_v2, olmoe

CONFIGS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", "configs")


def cfg(name):
    return json.load(open(os.path.join(CONFIGS, name + ".json")))


def test_olmoe_hand_count():
    c = cfg("olmoe-1b-7b")
    proj = 4 * 2 * 2048 * 2048              # q, k, v, o: 16 heads of 128
    attn = 4 * 16 * 128 * 100               # scores and values, 100 keys
    router = 2 * 2048 * 64
    experts = 8 * 3 * 2 * 2048 * 1024       # top-8 of 64, not all 64
    head = 2 * 2048 * 50304
    want = 16 * (proj + attn + router + experts) + head
    assert want == 2_370_830_336
    assert olmoe.flops_per_token(c, 100, head=True) == want
    assert olmoe.flops_per_token(c, 100, head=False) == want - head


def test_deepseek_v2_lite_13l_hand_count():
    c = cfg("deepseek-v2-lite-13l")
    attn = (2 * 2048 * 16 * 192             # q: nope 128 + rope 64
            + 2 * 2048 * 576                # latent 512 + rope key 64
            + 2 * 512 * 16 * 256            # latent to k_nope and v
            + 2 * 16 * 128 * 2048           # o
            + 2 * 16 * 192 * 100 + 2 * 16 * 128 * 100)
    dense = 3 * 2 * 2048 * 10944
    moe = 2 * 2048 * 64 + 6 * 3 * 2 * 2048 * 1408 + 2 * 3 * 2 * 2048 * 1408
    head = 2 * 2048 * 102400
    want = 13 * attn + dense + 12 * moe + head
    assert want == 2_589_138_944
    assert deepseek_v2.flops_per_token(c, 100, head=True) == want
