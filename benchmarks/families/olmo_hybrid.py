"""Olmo-Hybrid family: the published ``config.json`` keys -> the torch
module the paper's path constructs
(``torchdistx_tpu/models/olmo_hybrid_torch.py``: the installed
``transformers`` has no ``olmo_hybrid``), the repo's native model, and the
counts from shapes the per-layer metrics need.

``layer_types`` gives each layer's kind; ``full_attn_period`` with
``full_attn_offset`` says the same in numbers, for the reference
(``reference/olmo_hybrid.py``), and the two must agree."""

REFERENCE = "olmo_hybrid"


def kinds(sizes: dict) -> tuple:
    from reference import olmo_hybrid as ref

    n = sizes["num_hidden_layers"]
    listed = tuple(sizes["layer_types"])
    by_rule = tuple(ref.kind_of(i, sizes) for i in range(n))
    if listed != by_rule:
        raise ValueError(
            f"layer_types {listed} disagree with full_attn_offset "
            f"{sizes['full_attn_offset']} every {sizes['full_attn_period']} "
            f"layers or with num_hidden_layers {n}"
        )
    return listed


def hf(sizes: dict):
    """``(build, config)`` for ``deferred_init(build, config)``."""
    from torchdistx_tpu.models.olmo_hybrid_torch import (
        OlmoHybridConfig, OlmoHybridForCausalLM,
    )

    keys = (
        "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
        "num_attention_heads", "num_key_value_heads", "linear_num_key_heads",
        "linear_num_value_heads", "linear_key_head_dim", "linear_value_head_dim",
        "linear_conv_kernel_dim", "linear_allow_neg_eigval", "rms_norm_eps",
        "tie_word_embeddings",
    )
    config = OlmoHybridConfig(
        layer_types=kinds(sizes), **{k: sizes[k] for k in keys}
    )
    return OlmoHybridForCausalLM, config


def native(sizes: dict, dtype):
    from torchdistx_tpu.models import olmo_hybrid

    if sizes["linear_num_key_heads"] != sizes["linear_num_value_heads"]:
        raise ValueError("models/olmo_hybrid.py runs as many key heads as value heads")
    if sizes["num_key_value_heads"] != sizes["num_attention_heads"]:
        raise ValueError("models/olmo_hybrid.py runs plain multi-head attention")
    return olmo_hybrid, olmo_hybrid.OlmoHybridConfig(
        vocab_size=sizes["vocab_size"], dim=sizes["hidden_size"],
        n_layers=sizes["num_hidden_layers"],
        n_heads=sizes["num_attention_heads"],
        head_dim=sizes["hidden_size"] // sizes["num_attention_heads"],
        linear_heads=sizes["linear_num_key_heads"],
        linear_key_dim=sizes["linear_key_head_dim"],
        linear_value_dim=sizes["linear_value_head_dim"],
        conv_taps=sizes["linear_conv_kernel_dim"],
        allow_neg_eigval=sizes["linear_allow_neg_eigval"],
        ffn_dim=sizes["intermediate_size"], layer_types=kinds(sizes),
        norm_eps=sizes["rms_norm_eps"],
        delta_chunk=sizes["training"]["delta_chunk"], dtype=dtype,
    )


def to_params(arrays: dict, cfg):
    from torchdistx_tpu.models import convert

    return convert.olmo_hybrid_params_from_hf(arrays, cfg)


def counts(sizes: dict) -> dict:
    """From shapes.  ``matmul_params``: parameters a token multiplies
    (every projection, the gates, the feed-forward, the head; looked-up
    embeddings do no arithmetic; the convolutions' taps are element-wise
    work and not counted).

    ``d_attn``: ``shapes.train_flops_per_token`` charges every layer ``6 *
    d_attn * (seq + 1)``, the WHOLE causal triangle at ``d_attn`` per key,
    so it is set from what the layers REQUIRE over the triangles': a full
    layer its causal pairs at ``2 * hidden_size`` (QK^T and PV), a linear
    layer the delta rule's recurrent form, ``6 * d_k * d_v`` a head and
    token forward (the decayed state's ``k^T S``, the rank-1 correction and
    ``S^T q``), rounded down: ``train_mfu_pct`` counts no masked product
    and none of the chunk form's extra work."""
    d, ff = sizes["hidden_size"], sizes["intermediate_size"]
    h, dk, dv = (
        sizes["linear_num_key_heads"], sizes["linear_key_head_dim"],
        sizes["linear_value_head_dim"],
    )
    layer_kinds = kinds(sizes)
    n_full = sum(k == "full_attention" for k in layer_kinds)
    n_linear = len(layer_kinds) - n_full
    full_layer = 4 * d * d + 3 * d * ff
    linear_layer = d * h * (2 * dk + 3 * dv + 2) + 3 * d * ff
    seq = sizes["training"]["seq"]
    triangle = seq * (seq + 1) // 2
    # a layer's FORWARD mixing FLOPs a sequence, 2 a multiply-add
    full_work = 2 * 2 * d * triangle
    linear_work = 6 * dk * dv * h * seq
    # forward + backward is 3 x forward = 6 * d_attn * (seq + 1) * seq / 2
    d_attn = (n_full * full_work + n_linear * linear_work) // (
        4 * len(layer_kinds) * triangle
    )
    return {
        "matmul_params": int(
            n_full * full_layer + n_linear * linear_layer + sizes["vocab_size"] * d
        ),
        "n_layers": len(layer_kinds),
        "d_attn": int(d_attn),
        "n_full_layers": n_full,
        "n_linear_layers": n_linear,
    }
