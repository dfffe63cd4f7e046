"""AFMoE family: the published ``config.json`` keys -> the torch module the
paper's path constructs (``torchdistx_tpu/models/afmoe_torch.py``: the
installed ``transformers`` has no ``afmoe``; ALL experts fake, the absent
ones dropped before anything is materialized), the repo's native model, and
the counts from shapes the per-layer metrics need.

The configuration's ``num_experts`` is the number of experts HELD here;
``num_experts_total`` is the published count, the router's width;
``first_expert_held`` the first of the contiguous share.  ``layer_types``
names each layer's kind; ``first_full_layer`` with the published
``global_attn_every_n_layers`` says the same in numbers, for the reference
(``reference/afmoe.py``), and the two must agree."""

REFERENCE = "afmoe"
HF_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_hidden_layers", "num_dense_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "num_experts_per_tok",
    "num_shared_experts", "route_norm", "route_scale", "score_func",
    "sliding_window", "global_attn_every_n_layers", "rope_theta",
    "rms_norm_eps", "mup_enabled", "tie_word_embeddings",
)


def _kinds(sizes: dict) -> tuple:
    from reference import afmoe as ref

    kinds = tuple(sizes["layer_types"])
    numbers = {k: v for k, v in sizes.items() if k != "layer_types"}
    by_rule = tuple(ref.kind_of(i, numbers) for i in range(len(kinds)))
    if kinds != by_rule or len(kinds) != sizes["num_hidden_layers"]:
        raise ValueError(
            f"layer_types {kinds} disagree with first_full_layer "
            f"{sizes['first_full_layer']} every "
            f"{sizes['global_attn_every_n_layers']} layers ({by_rule}) or "
            f"with num_hidden_layers {sizes['num_hidden_layers']}"
        )
    return kinds


def hf(sizes: dict):
    """``(build, config)`` for ``deferred_init(build, config)``: the whole
    layer is constructed fake, with every published expert, and the
    experts that live on other chips are deleted before anything is
    materialized — their fills are on the tape and never run."""
    from torchdistx_tpu.models.afmoe_torch import AfmoeConfig, AfmoeForCausalLM

    first, held = sizes["first_expert_held"], sizes["num_experts"]
    config = AfmoeConfig(
        num_experts=sizes["num_experts_total"], layer_types=_kinds(sizes),
        **{k: sizes[k] for k in HF_KEYS},
    )

    def build(config):
        module = AfmoeForCausalLM(config)
        for layer in module.model.layers[config.num_dense_layers:]:
            experts = layer.mlp.experts
            del experts[first + held:]
            del experts[:first]
        return module

    return build, config


def native(sizes: dict, dtype):
    from torchdistx_tpu.models import afmoe

    dense = sizes["num_dense_layers"]
    return afmoe, afmoe.AfmoeConfig(
        vocab_size=sizes["vocab_size"], dim=sizes["hidden_size"],
        n_dense_layers=dense, n_moe_layers=sizes["num_hidden_layers"] - dense,
        n_heads=sizes["num_attention_heads"],
        n_kv_heads=sizes["num_key_value_heads"], head_dim=sizes["head_dim"],
        ffn_dim=sizes["intermediate_size"],
        expert_dim=sizes["moe_intermediate_size"],
        shared_dim=sizes["moe_intermediate_size"] * sizes["num_shared_experts"],
        n_experts=sizes["num_experts_total"],
        experts_per_token=sizes["num_experts_per_tok"],
        routed_scale=sizes["route_scale"],
        n_experts_held=sizes["num_experts"],
        first_expert_held=sizes["first_expert_held"],
        window=sizes["sliding_window"], layer_types=_kinds(sizes),
        rope_theta=float(sizes["rope_theta"]), norm_eps=sizes["rms_norm_eps"],
        embed_scale=sizes["mup_enabled"], dtype=dtype,
    )


def to_params(arrays: dict, cfg):
    from torchdistx_tpu.models import convert

    return convert.afmoe_params_from_hf(arrays, cfg)


def counts(sizes: dict) -> dict:
    """From shapes.  ``matmul_params``: parameters a token multiplies HERE.
    Per layer the five attention projections (``W_q``, ``W_k``, ``W_v``,
    the gate's, ``W_o``); in a dense layer its feed-forward; in an expert
    layer the router, the shared expert, and of the routed experts
    ``num_experts_per_tok`` times the share held (in expectation under
    uniform routing: a token's other choices run on other chips); the head
    over the vocabulary held (looked-up embeddings do no arithmetic).

    ``d_attn``: ``shapes.train_flops_per_token`` charges every layer ``6 *
    d_attn * (seq + 1)``, the WHOLE causal triangle, so the heads' width is
    scaled by the pairs the layers REQUIRE over the triangles' (a window
    layer meets ``banded.window_pairs`` of them), rounded down:
    ``train_mfu_pct`` counts no masked product."""
    from benchlib import banded

    d, h, hd = sizes["hidden_size"], sizes["num_attention_heads"], sizes["head_dim"]
    attn = d * hd * (3 * h + 2 * sizes["num_key_value_heads"])
    dense = sizes["num_dense_layers"]
    moe = sizes["num_hidden_layers"] - dense
    expert = 3 * d * sizes["moe_intermediate_size"]
    share = sizes["num_experts"] / sizes["num_experts_total"]
    per_moe = (
        attn + d * sizes["num_experts_total"]
        + sizes["num_shared_experts"] * expert
        + sizes["num_experts_per_tok"] * share * expert
    )
    per_dense = attn + 3 * d * sizes["intermediate_size"]
    seq, kinds = sizes["training"]["seq"], _kinds(sizes)
    n_window = sum(k == "sliding_attention" for k in kinds)
    triangle = seq * (seq + 1) // 2
    required = (
        n_window * banded.window_pairs(seq, sizes["sliding_window"])
        + (len(kinds) - n_window) * triangle
    )
    return {
        "matmul_params": int(
            dense * per_dense + moe * per_moe + sizes["vocab_size"] * d
        ),
        "n_layers": dense + moe,
        "d_attn": h * hd * required // (len(kinds) * triangle),
        "attn_params": attn,
        "expert_params": expert,
        "n_moe_layers": moe,
        "n_window_layers": n_window,
    }
