"""DeepSeek-V3 family: the published ``config.json`` keys -> the Hugging
Face module the paper's path constructs (ALL experts fake, the absent ones
dropped before anything is materialized), the repo's native model, and the
counts from shapes the per-layer metrics need.

The configuration's ``n_routed_experts`` is the number of experts HELD
here; ``n_routed_experts_total`` is the published count, the router's
width; ``first_expert_held`` the first of the contiguous share."""

REFERENCE = "deepseek_v3"
HF_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
    "n_shared_experts", "routed_scaling_factor", "kv_lora_rank", "q_lora_rank",
    "qk_rope_head_dim", "v_head_dim", "qk_nope_head_dim", "n_group",
    "topk_group", "num_experts_per_tok", "first_k_dense_replace",
    "norm_topk_prob", "hidden_act", "max_position_embeddings",
    "rms_norm_eps", "tie_word_embeddings", "rope_theta", "rope_scaling",
    "rope_interleave", "attention_bias",
)


def hf(sizes: dict):
    """``(build, config)`` for ``deferred_init(build, config)``: the whole
    layer is constructed fake, with every published expert, and the
    experts that live on other chips are deleted before anything is
    materialized — their fills are on the tape and never run."""
    from transformers import DeepseekV3Config, DeepseekV3ForCausalLM

    first, held = sizes["first_expert_held"], sizes["n_routed_experts"]
    config = DeepseekV3Config(
        n_routed_experts=sizes["n_routed_experts_total"],
        **{k: sizes[k] for k in HF_KEYS},
    )

    def build(config):
        module = DeepseekV3ForCausalLM(config)
        for layer in module.model.layers[config.first_k_dense_replace:]:
            experts = layer.mlp.experts
            del experts[first + held:]
            del experts[:first]
        return module

    return build, config


def native(sizes: dict, dtype):
    from torchdistx_tpu.models import deepseek_v3

    dense = sizes["first_k_dense_replace"]
    return deepseek_v3, deepseek_v3.DeepseekV3Config(
        vocab_size=sizes["vocab_size"], dim=sizes["hidden_size"],
        n_dense_layers=dense, n_moe_layers=sizes["num_hidden_layers"] - dense,
        n_heads=sizes["num_attention_heads"],
        qk_nope_dim=sizes["qk_nope_head_dim"],
        qk_rope_dim=sizes["qk_rope_head_dim"], v_dim=sizes["v_head_dim"],
        kv_rank=sizes["kv_lora_rank"], ffn_dim=sizes["intermediate_size"],
        expert_dim=sizes["moe_intermediate_size"],
        shared_dim=sizes["moe_intermediate_size"] * sizes["n_shared_experts"],
        n_experts=sizes["n_routed_experts_total"],
        experts_per_token=sizes["num_experts_per_tok"],
        routed_scale=sizes["routed_scaling_factor"],
        n_experts_held=sizes["n_routed_experts"],
        first_expert_held=sizes["first_expert_held"],
        rope_theta=float(sizes["rope_theta"]), norm_eps=sizes["rms_norm_eps"],
        dtype=dtype,
    )


def to_params(arrays: dict, cfg):
    from torchdistx_tpu.models import convert

    return convert.deepseek_v3_params_from_hf(arrays, cfg)


def counts(sizes: dict) -> dict:
    """From shapes.  ``matmul_params``: parameters a token multiplies HERE.
    Per layer the attention projections (``W_q``, ``W_kva``, ``W_kvb``,
    ``W_o``); in a dense layer its feed-forward; in an expert layer the
    router, the shared experts, and of the routed experts
    ``num_experts_per_tok`` times the share held (in expectation under
    uniform routing: a token's other choices run on other chips); the head
    over the vocabulary held (looked-up embeddings do no arithmetic).
    ``d_attn``: the mean of the q/k and the v head widths times the heads,
    so that ``shapes.train_flops_per_token`` counts QK^T at the first and
    PV at the second."""
    d, h = sizes["hidden_size"], sizes["num_attention_heads"]
    qk = sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]
    nope, v = sizes["qk_nope_head_dim"], sizes["v_head_dim"]
    rank = sizes["kv_lora_rank"]
    attn = (
        d * h * qk + d * (rank + sizes["qk_rope_head_dim"])
        + rank * h * (nope + v) + h * v * d
    )
    dense = sizes["first_k_dense_replace"]
    moe = sizes["num_hidden_layers"] - dense
    expert = 3 * d * sizes["moe_intermediate_size"]
    share = sizes["n_routed_experts"] / sizes["n_routed_experts_total"]
    per_moe = (
        attn + d * sizes["n_routed_experts_total"]
        + sizes["n_shared_experts"] * expert
        + sizes["num_experts_per_tok"] * share * expert
    )
    per_dense = attn + 3 * d * sizes["intermediate_size"]
    return {
        "matmul_params": int(
            dense * per_dense + moe * per_moe + sizes["vocab_size"] * d
        ),
        "n_layers": dense + moe,
        "d_attn": h * (qk + v) // 2,
        "attn_params": attn,
        "expert_params": expert,
        "n_moe_layers": moe,
    }
