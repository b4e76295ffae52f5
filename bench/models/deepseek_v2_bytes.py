"""Bytes that DeepSeek-V2's latent-attention decode needs from device
memory, for the roofline share of the program's MLA decode modules.

Counted for the MoE layers only: the dense first layer decodes in a module
of its own that also holds its feed-forward. Per decode step, each MoE
layer reads its attention weights once (query `wq`, latent and rotary-key
projection `wkv_a`, latent expansion `wkv_b`, output `wo`) in the
configuration's dtype, its float32 router and its three norms (attention,
latent, feed-forward). Per decoded token, each MoE layer reads the latent
and rotary-key rows of every position its context holds. Anything else a
module reads (padding rows, cache rows past a context) is not needed work
and is not counted.
"""
from __future__ import annotations

from typing import Iterable

from bench.models.deepseek_v2 import dtype, is_moe


def n_moe_layers(cfg) -> int:
    return sum(1 for i in range(cfg["num_hidden_layers"]) if is_moe(cfg, i))


def layer_weight_bytes(cfg) -> int:
    """One MoE layer's attention weights, router and norms, in bytes."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope_d, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                        cfg["v_head_dim"])
    R, E = cfg["kv_lora_rank"], cfg["n_routed_experts"]
    w = dtype(cfg).itemsize
    attn = (d * H * (nope + rope_d) + d * (R + rope_d) + R * H * (nope + vd)
            + H * vd * d)
    norms = d + R + d
    return w * (attn + norms) + 4 * d * E


def position_bytes(cfg) -> int:
    """One cached position of one layer: its latent and rotary-key rows."""
    return dtype(cfg).itemsize * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


def decode_bytes(cfg, steps: int, ctx_lens: Iterable[int]) -> int:
    """Bytes `steps` decode steps need, whose decoded tokens attended to
    `ctx_lens` positions each, over every MoE layer."""
    return n_moe_layers(cfg) * (steps * layer_weight_bytes(cfg)
                                + position_bytes(cfg) * int(sum(ctx_lens)))
