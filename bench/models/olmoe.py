"""Plain reference of OLMoE (arXiv:2409.02060; model_type `olmoe`).

Every layer: RMSNorm, multi-head attention with RMSNorm on queries and keys
and rotary positions, RMSNorm, then a softmax router over all experts whose
top-k SwiGLU experts are summed with their router probabilities
(`norm_topk_prob` false: not renormalized). Final RMSNorm and an untied
output head.

Departure, as the program runs it: the query and key norms act on each
head's `head_dim` values with a weight of that width. The published model
normalizes the whole `hidden_size`-wide projection before splitting heads.
"""
from __future__ import annotations

import jax.numpy as jnp

from bench.models.common import causal_attention, mm, rms_norm, rope


def dtype(cfg):
    return jnp.dtype(cfg["torch_dtype"])


def head_dim(cfg):
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def is_moe(cfg, i):
    return True


def top_spec(cfg):
    d, V, dt = cfg["hidden_size"], cfg["vocab_size"], dtype(cfg)
    return {"embed": ((V, d), "embed", 1, dt),
            "final_norm": ((d,), "norm", 1, dt),
            "lm_head": ((d, V), "w", d, dt)}


def layer_spec(cfg, i):
    d, H, Hkv = (cfg["hidden_size"], cfg["num_attention_heads"],
                 cfg["num_key_value_heads"])
    hd, E, f, dt = head_dim(cfg), cfg["num_experts"], cfg["intermediate_size"], dtype(cfg)
    return {"attn_norm": ((d,), "norm", 1, dt),
            "wq": ((d, H, hd), "w", d, dt),
            "wk": ((d, Hkv, hd), "w", d, dt),
            "wv": ((d, Hkv, hd), "w", d, dt),
            "wo": ((H, hd, d), "w", H * hd, dt),
            "q_norm": ((hd,), "norm", 1, dt),
            "k_norm": ((hd,), "norm", 1, dt),
            "ffn_norm": ((d,), "norm", 1, dt),
            "router": ((d, E), "w", d, jnp.float32),
            "e_gate": ((E, d, f), "w", d, dt),
            "e_up": ((E, d, f), "w", d, dt),
            "e_down": ((E, f, d), "w", f, dt)}


def attention(cfg, w, h, positions, prec):
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    q = rms_norm(mm("btd,dhk->bthk", h, w["wq"], prec), w["q_norm"], eps)
    k = rms_norm(mm("btd,dhk->bthk", h, w["wk"], prec), w["k_norm"], eps)
    v = mm("btd,dhk->bthk", h, w["wv"], prec)
    q, k = rope(q, positions, theta), rope(k, positions, theta)
    G = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2)
    out = causal_attention(q, k, v, head_dim(cfg) ** -0.5)
    return mm("bthk,hkd->btd", out, w["wo"], prec)


def flops_per_token(cfg, keys: int, head: bool) -> float:
    """Needed FLOPs of one token that attends to `keys` positions: 2 x the
    weights it multiplies (its top-k experts only), plus attention."""
    d, H, Hkv = (cfg["hidden_size"], cfg["num_attention_heads"],
                 cfg["num_key_value_heads"])
    hd, E, f, k = (head_dim(cfg), cfg["num_experts"], cfg["intermediate_size"],
                   cfg["num_experts_per_tok"])
    layer = (2 * d * H * hd * 2 + 2 * d * Hkv * hd * 2      # q, o; k, v
             + 4 * H * hd * keys                            # scores, values
             + 2 * d * E + k * 3 * 2 * d * f)               # router, experts
    return cfg["num_hidden_layers"] * layer + (2 * d * cfg["vocab_size"] if head else 0)
