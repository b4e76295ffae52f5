"""Plain reference of DeepSeek-V2 (arXiv:2405.04434; model_type `deepseek_v2`).

Multi-head latent attention without a query low-rank path: the query is
projected to per-head `qk_nope_head_dim + qk_rope_head_dim` values; keys and
values come from a `kv_lora_rank`-wide latent (RMS-normed) expanded per head,
with one rotary key of `qk_rope_head_dim` shared by all heads; softmax scale
(qk_nope_head_dim + qk_rope_head_dim) ** -0.5. The first
`first_k_dense_replace` layers have a SwiGLU MLP of `intermediate_size`;
the others a softmax router over `n_routed_experts` (top
`num_experts_per_tok`, gates not renormalized when `norm_topk_prob` is
false, times `routed_scaling_factor`) plus `n_shared_experts` always-on
experts, one SwiGLU of `n_shared_experts * moe_intermediate_size`.

Rotary positions act on the two halves of each rotary vector; the published
checkpoint interleaves pairs instead, which is a fixed permutation of the
weights' columns and the same function of the positions. The keys that the
configuration file lists under `reduced` are run as that file states.
"""
from __future__ import annotations

import jax.numpy as jnp

from bench.models.common import causal_attention, mm, rms_norm, rope


def dtype(cfg):
    return jnp.dtype(cfg["torch_dtype"])


def is_moe(cfg, i):
    return i >= cfg["first_k_dense_replace"] and \
        (i - cfg["first_k_dense_replace"]) % cfg["moe_layer_freq"] == 0


def top_spec(cfg):
    d, V, dt = cfg["hidden_size"], cfg["vocab_size"], dtype(cfg)
    return {"embed": ((V, d), "embed", 1, dt),
            "final_norm": ((d,), "norm", 1, dt),
            "lm_head": ((d, V), "w", d, dt)}


def layer_spec(cfg, i):
    assert not cfg["q_lora_rank"], "query low-rank path not written"
    d, H, dt = cfg["hidden_size"], cfg["num_attention_heads"], dtype(cfg)
    nope, rope_d, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                        cfg["v_head_dim"])
    R = cfg["kv_lora_rank"]
    spec = {"attn_norm": ((d,), "norm", 1, dt),
            "wq": ((d, H, nope + rope_d), "w", d, dt),
            "wkv_a": ((d, R + rope_d), "w", d, dt),
            "kv_a_norm": ((R,), "norm", 1, dt),
            "wkv_b": ((R, H, nope + vd), "w", R, dt),
            "wo": ((H, vd, d), "w", H * vd, dt),
            "ffn_norm": ((d,), "norm", 1, dt)}
    if not is_moe(cfg, i):
        F = cfg["intermediate_size"]
        spec.update(f_gate=((d, F), "w", d, dt), f_up=((d, F), "w", d, dt),
                    f_down=((F, d), "w", F, dt))
        return spec
    E, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    fs = f * cfg["n_shared_experts"]
    spec.update(router=((d, E), "w", d, jnp.float32),
                e_gate=((E, d, f), "w", d, dt), e_up=((E, d, f), "w", d, dt),
                e_down=((E, f, d), "w", f, dt))
    if fs:
        spec.update(s_gate=((d, fs), "w", d, dt), s_up=((d, fs), "w", d, dt),
                    s_down=((fs, d), "w", fs, dt))
    return spec


def attention(cfg, w, h, positions, prec):
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    nope, rope_d = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    R = cfg["kv_lora_rank"]
    q = mm("btd,dhk->bthk", h, w["wq"], prec)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], positions, theta)], -1)
    kv_a = mm("btd,dr->btr", h, w["wkv_a"], prec)
    c = rms_norm(kv_a[..., :R], w["kv_a_norm"], eps)
    k_pe = rope(kv_a[..., None, R:], positions, theta)        # (B, T, 1, r)
    kv = mm("btr,rhk->bthk", c, w["wkv_b"], prec)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, k_nope.shape[:3] + (rope_d,))], -1)
    out = causal_attention(q, k, v, (nope + rope_d) ** -0.5)
    return mm("bthk,hkd->btd", out, w["wo"], prec)


def flops_per_token(cfg, keys: int, head: bool) -> float:
    """Needed FLOPs of one token that attends to `keys` positions: 2 x the
    weights it multiplies (its top-k routed experts only, the shared ones,
    its own latent's expansion), plus attention."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope_d, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                        cfg["v_head_dim"])
    R = cfg["kv_lora_rank"]
    attn = (2 * d * H * (nope + rope_d) + 2 * d * (R + rope_d)
            + 2 * R * H * (nope + vd) + 2 * H * vd * d
            + 2 * H * (nope + rope_d) * keys + 2 * H * vd * keys)
    total = 0.0
    for i in range(cfg["num_hidden_layers"]):
        if is_moe(cfg, i):
            f, k = cfg["moe_intermediate_size"], cfg["num_experts_per_tok"]
            ffn = (2 * d * cfg["n_routed_experts"] + k * 6 * d * f
                   + 6 * d * f * cfg["n_shared_experts"])
        else:
            ffn = 6 * d * cfg["intermediate_size"]
        total += attn + ffn
    return total + (2 * d * cfg["vocab_size"] if head else 0)
