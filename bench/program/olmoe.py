"""The system under test's view of an `olmoe` configuration: its
`ModelConfig`, and the benchmark's tensors placed where its parameter
tree keeps them."""
from __future__ import annotations

from repro.configs.base import MoEConfig, ModelConfig


def config(cfg: dict) -> ModelConfig:
    return ModelConfig(
        name=cfg["name"], family="moe", num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], d_ff=0,
        vocab_size=cfg["vocab_size"], qk_norm=True,
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        dtype=cfg["torch_dtype"],
        moe=MoEConfig(num_experts=cfg["num_experts"],
                      top_k=cfg["num_experts_per_tok"],
                      d_expert=cfg["intermediate_size"],
                      router_norm_topk=cfg["norm_topk_prob"]))


def top(w: dict) -> dict:
    return {"embed": w["embed"], "final_norm": w["final_norm"],
            "lm_head": w["lm_head"]}


def layer(cfg: dict, i: int, w: dict):
    """(the layer's device parameters, its routed experts or None)."""
    p = {"pre_norm": w["attn_norm"],
         "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo", "q_norm", "k_norm")},
         "ffn_norm": w["ffn_norm"],
         "moe": {"router": w["router"]}}
    return p, (w["e_gate"], w["e_up"], w["e_down"])
