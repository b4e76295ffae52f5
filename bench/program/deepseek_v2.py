"""The system under test's view of a `deepseek_v2` configuration: its
`ModelConfig`, and the benchmark's tensors placed where its parameter
tree keeps them."""
from __future__ import annotations

from repro.configs.base import MLAConfig, MoEConfig, ModelConfig

from bench.models.deepseek_v2 import is_moe


def config(cfg: dict) -> ModelConfig:
    assert cfg["routed_scaling_factor"] == 1, "the program has no such factor"
    assert cfg["moe_layer_freq"] == 1 and cfg["scoring_func"] == "softmax"
    return ModelConfig(
        name=cfg["name"], family="moe", num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        attention="mla",
        mla=MLAConfig(q_lora_rank=cfg["q_lora_rank"] or 0,
                      kv_lora_rank=cfg["kv_lora_rank"],
                      qk_nope_head_dim=cfg["qk_nope_head_dim"],
                      qk_rope_head_dim=cfg["qk_rope_head_dim"],
                      v_head_dim=cfg["v_head_dim"]),
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"], dtype=cfg["torch_dtype"],
        moe=MoEConfig(num_experts=cfg["n_routed_experts"],
                      top_k=cfg["num_experts_per_tok"],
                      d_expert=cfg["moe_intermediate_size"],
                      num_shared_experts=cfg["n_shared_experts"],
                      d_shared=cfg["moe_intermediate_size"],
                      router_norm_topk=cfg["norm_topk_prob"],
                      first_dense_layers=cfg["first_k_dense_replace"]))


def top(w: dict) -> dict:
    return {"embed": w["embed"], "final_norm": w["final_norm"],
            "lm_head": w["lm_head"]}


def layer(cfg: dict, i: int, w: dict):
    """(the layer's device parameters, its routed experts or None)."""
    p = {"pre_norm": w["attn_norm"],
         "attn": {k: w[k] for k in ("wq", "wkv_a", "kv_a_norm", "wkv_b", "wo")},
         "ffn_norm": w["ffn_norm"]}
    if not is_moe(cfg, i):
        p["ffn"] = {"w_gate": w["f_gate"], "w_up": w["f_up"],
                    "w_down": w["f_down"]}
        return p, None
    p["moe"] = {"router": w["router"]}
    if "s_gate" in w:
        p["moe"]["shared"] = {"w_gate": w["s_gate"], "w_up": w["s_up"],
                              "w_down": w["s_down"]}
    return p, (w["e_gate"], w["e_up"], w["e_down"])
