"""SmallThinker family: the published ``config.json`` keys -> the torch
module the paper's path constructs
(``torchdistx_tpu/models/smallthinker_torch.py``: the installed
``transformers`` has no ``smallthinker``; ALL experts fake, the absent ones
dropped before anything is materialized), the repo's native model, and the
counts from shapes the per-layer metrics need.

The configuration's ``moe_num_primary_experts`` is the number of experts
HELD here; ``moe_num_primary_experts_total`` is the published count, the
router's width; ``first_expert_held`` the first of the contiguous share.
``sliding_window_layout`` / ``rope_layout`` give each layer's kind (1: the
layer slides / ropes); ``first_full_layer`` with ``full_attn_every_n_layers``
says the same in numbers, for the reference (``reference/smallthinker.py``),
and the two must agree."""

REFERENCE = "smallthinker"
HF_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "num_key_value_heads", "head_dim", "moe_ffn_hidden_size",
    "moe_num_active_primary_experts", "moe_primary_router_apply_softmax",
    "norm_topk_prob", "sliding_window_size", "sliding_window_layout",
    "rope_layout", "rope_theta", "rms_norm_eps", "tie_word_embeddings",
)
WINDOW, FULL = "sliding_attention", "full_attention"


def _kinds(sizes: dict) -> tuple:
    from reference import smallthinker as ref

    n = sizes["num_hidden_layers"]
    slides, ropes = sizes["sliding_window_layout"], sizes["rope_layout"]
    numbers = {k: v for k, v in sizes.items() if not k.endswith("_layout")}
    by_rule = [ref.kind_of(i, numbers) for i in range(n)]
    if len(slides) != n or [(bool(s), bool(r)) for s, r in zip(slides, ropes)] != by_rule:
        raise ValueError(
            f"sliding_window_layout {slides} / rope_layout {ropes} disagree "
            f"with first_full_layer {sizes['first_full_layer']} every "
            f"{sizes['full_attn_every_n_layers']} layers or with "
            f"num_hidden_layers {n}"
        )
    # a layer that slides ropes, and the others do neither (ref.kind_of)
    return tuple(WINDOW if s else FULL for s in slides)


def hf(sizes: dict):
    """``(build, config)`` for ``deferred_init(build, config)``: the whole
    layer is constructed fake, with every published expert, and the
    experts that live on other chips are deleted before anything is
    materialized — their fills are on the tape and never run."""
    from torchdistx_tpu.models.smallthinker_torch import (
        SmallThinkerConfig, SmallThinkerForCausalLM,
    )

    _kinds(sizes)
    first, held = sizes["first_expert_held"], sizes["moe_num_primary_experts"]
    config = SmallThinkerConfig(
        moe_num_primary_experts=sizes["moe_num_primary_experts_total"],
        **{k: sizes[k] for k in HF_KEYS},
    )

    def build(config):
        module = SmallThinkerForCausalLM(config)
        for layer in module.model.layers:
            experts = layer.block_sparse_moe.experts
            del experts[first + held:]
            del experts[:first]
        return module

    return build, config


def native(sizes: dict, dtype):
    from torchdistx_tpu.models import smallthinker

    return smallthinker, smallthinker.SmallThinkerConfig(
        vocab_size=sizes["vocab_size"], dim=sizes["hidden_size"],
        n_layers=sizes["num_hidden_layers"],
        n_heads=sizes["num_attention_heads"],
        n_kv_heads=sizes["num_key_value_heads"], head_dim=sizes["head_dim"],
        expert_dim=sizes["moe_ffn_hidden_size"],
        n_experts=sizes["moe_num_primary_experts_total"],
        experts_per_token=sizes["moe_num_active_primary_experts"],
        n_experts_held=sizes["moe_num_primary_experts"],
        first_expert_held=sizes["first_expert_held"],
        window=sizes["sliding_window_size"], layer_types=_kinds(sizes),
        rope_theta=float(sizes["rope_theta"]), norm_eps=sizes["rms_norm_eps"],
        dtype=dtype,
    )


def to_params(arrays: dict, cfg):
    from torchdistx_tpu.models import convert

    return convert.smallthinker_params_from_hf(arrays, cfg)


def counts(sizes: dict) -> dict:
    """From shapes.  ``matmul_params``: parameters a token multiplies HERE.
    Per layer the four attention projections, the router, and of the
    routed experts ``moe_num_active_primary_experts`` times the share held
    (in expectation under uniform routing: a token's other choices run on
    other chips); the head over the vocabulary held (looked-up embeddings
    do no arithmetic).

    ``d_attn``: ``shapes.train_flops_per_token`` charges every layer ``6 *
    d_attn * (seq + 1)``, the WHOLE causal triangle, so the heads' width is
    scaled by the pairs the layers REQUIRE over the triangles' (a window
    layer meets ``banded.window_pairs`` of them), rounded down:
    ``train_mfu_pct`` counts no masked product."""
    from benchlib import banded

    d, h, hd = sizes["hidden_size"], sizes["num_attention_heads"], sizes["head_dim"]
    attn = d * hd * (2 * h + 2 * sizes["num_key_value_heads"])
    expert = 3 * d * sizes["moe_ffn_hidden_size"]
    total = sizes["moe_num_primary_experts_total"]
    share = sizes["moe_num_primary_experts"] / total
    per_layer = (
        attn + d * total
        + sizes["moe_num_active_primary_experts"] * share * expert
    )
    seq, kinds = sizes["training"]["seq"], _kinds(sizes)
    n_window = sum(k == WINDOW for k in kinds)
    triangle = seq * (seq + 1) // 2
    required = (
        n_window * banded.window_pairs(seq, sizes["sliding_window_size"])
        + (len(kinds) - n_window) * triangle
    )
    return {
        "matmul_params": int(len(kinds) * per_layer + sizes["vocab_size"] * d),
        "n_layers": len(kinds),
        "d_attn": h * hd * required // (len(kinds) * triangle),
        "attn_params": attn,
        "expert_params": expert,
        "n_moe_layers": len(kinds),
        "n_window_layers": n_window,
        "n_full_layers": len(kinds) - n_window,
    }
