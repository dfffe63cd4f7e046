"""MiniCPM-SALA family: the published ``config.json`` keys -> the torch
module the paper's path constructs
(``torchdistx_tpu/models/minicpm_sala_torch.py``: the installed
``transformers`` has no ``minicpm_sala``), the repo's native model, and the
counts from shapes the per-layer metrics need.

``mixer_types`` gives each layer's kind; ``first_sparse_layer`` with
``sparse_every_n_layers`` says the same in numbers, for the reference
(``reference/minicpm_sala.py``), and the two must agree.  The row's
``sparse_config`` is absent: the flat keys ``sparse_*`` carry MiniCPM4-8B's
(the configuration's ``assumed`` says so), ``depth_scale_layers`` the
published depth the residual scale reads."""

REFERENCE = "minicpm_sala"
SPARSE_KEYS = (
    "kernel_size", "kernel_stride", "init_blocks", "block_size",
    "window_size", "topk", "dense_len",
)


def kinds(sizes: dict) -> tuple:
    from reference import minicpm_sala as ref

    n = sizes["num_hidden_layers"]
    listed = tuple(sizes["mixer_types"])
    numbers = {k: v for k, v in sizes.items() if k != "mixer_types"}
    by_rule = tuple(ref.kind_of(i, numbers) for i in range(n))
    if listed != by_rule:
        raise ValueError(
            f"mixer_types {listed} disagree with first_sparse_layer "
            f"{sizes['first_sparse_layer']} every "
            f"{sizes['sparse_every_n_layers']} layers or with "
            f"num_hidden_layers {n}"
        )
    return listed


def _sparse(sizes: dict) -> dict:
    return {k: sizes[f"sparse_{k}"] for k in SPARSE_KEYS}


def hf(sizes: dict):
    """``(build, config)`` for ``deferred_init(build, config)``."""
    from torchdistx_tpu.models.minicpm_sala_torch import (
        MiniCPMSALAConfig, MiniCPMSALAForCausalLM,
    )

    config = MiniCPMSALAConfig(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["hidden_size"],
        intermediate_size=sizes["intermediate_size"],
        num_hidden_layers=sizes["num_hidden_layers"],
        num_attention_heads=sizes["num_attention_heads"],
        num_key_value_heads=sizes["num_key_value_heads"],
        head_dim=sizes["head_dim"], lightning_nh=sizes["lightning_nh"],
        lightning_nkv=sizes["lightning_nkv"],
        lightning_head_dim=sizes["lightning_head_dim"],
        mixer_types=kinds(sizes), rope_theta=float(sizes["rope_theta"]),
        rms_norm_eps=sizes["rms_norm_eps"], scale_emb=float(sizes["scale_emb"]),
        scale_depth=float(sizes["scale_depth"]),
        residual_layers=sizes["depth_scale_layers"],
        dim_model_base=sizes["dim_model_base"],
        tie_word_embeddings=sizes["tie_word_embeddings"],
        **{f"sparse_{k}": v for k, v in _sparse(sizes).items()},
    )
    return MiniCPMSALAForCausalLM, config


def native(sizes: dict, dtype):
    from torchdistx_tpu.models import minicpm_sala
    from torchdistx_tpu.ops.sparse_attention import SparseConfig

    return minicpm_sala, minicpm_sala.MiniCPMSALAConfig(
        vocab_size=sizes["vocab_size"], dim=sizes["hidden_size"],
        n_layers=sizes["num_hidden_layers"],
        n_heads=sizes["num_attention_heads"],
        n_kv_heads=sizes["num_key_value_heads"], head_dim=sizes["head_dim"],
        lightning_heads=sizes["lightning_nh"],
        lightning_head_dim=sizes["lightning_head_dim"],
        ffn_dim=sizes["intermediate_size"], mixer_types=kinds(sizes),
        scale_emb=float(sizes["scale_emb"]),
        scale_depth=float(sizes["scale_depth"]),
        residual_layers=sizes["depth_scale_layers"],
        dim_model_base=sizes["dim_model_base"],
        rope_theta=float(sizes["rope_theta"]), norm_eps=sizes["rms_norm_eps"],
        sparse=SparseConfig(**_sparse(sizes)),
        lightning_chunk=sizes["training"].get("lightning_chunk", 256),
        dtype=dtype,
    )


def to_params(arrays: dict, cfg):
    from torchdistx_tpu.models import convert

    return convert.minicpm_sala_params_from_hf(arrays, cfg)


def counts(sizes: dict) -> dict:
    """From shapes.  ``matmul_params``: parameters a token multiplies
    (every projection, the gates, the feed-forward, the head; looked-up
    embeddings do no arithmetic).

    ``d_attn``: ``shapes.train_flops_per_token`` charges every layer ``6 *
    d_attn * (seq + 1)``, the WHOLE causal triangle at ``d_attn`` per key,
    so it is set from what the layers REQUIRE over the triangles': a sparse
    layer its chosen pairs at ``2 * H * head_dim`` (QK^T and PV,
    ``benchlib/sparse.py``), a lightning layer the quadratic form's pairs
    inside its chunks at the same width plus its state's products,
    ``4 * H * D * D`` a token (``q S`` and ``k^T v``), rounded down:
    ``train_mfu_pct`` counts no masked product."""
    from benchlib import sparse

    d, ff = sizes["hidden_size"], sizes["intermediate_size"]
    h, hkv, hd = (
        sizes["num_attention_heads"], sizes["num_key_value_heads"], sizes["head_dim"]
    )
    hl, hdl = sizes["lightning_nh"], sizes["lightning_head_dim"]
    layer_kinds = kinds(sizes)
    n_sparse = sum(k == "minicpm4" for k in layer_kinds)
    n_light = len(layer_kinds) - n_sparse
    sparse_layer = d * hd * (3 * h + 2 * hkv) + 3 * d * ff
    light_layer = 5 * d * hl * hdl + 3 * d * ff
    seq = sizes["training"]["seq"]
    chunk = sizes["training"].get("lightning_chunk", 256)
    triangle = seq * (seq + 1) // 2
    # a layer's FORWARD attention FLOPs a sequence, 2 a multiply-add
    sparse_work = 2 * 2 * h * hd * sparse.chosen_pairs(
        seq, sizes["sparse_block_size"], sizes["sparse_topk"],
        sizes["sparse_dense_len"],
    )
    light_work = 2 * 2 * hl * hdl * sparse.chunk_pairs(seq, chunk) + (
        2 * 2 * hl * hdl * hdl * seq
    )
    # forward + backward is 3 x forward = 6 * d_attn * (seq + 1) * seq / 2
    d_attn = (n_sparse * sparse_work + n_light * light_work) // (
        4 * len(layer_kinds) * triangle
    )
    return {
        "matmul_params": int(
            n_sparse * sparse_layer + n_light * light_layer + sizes["vocab_size"] * d
        ),
        "n_layers": len(layer_kinds),
        "d_attn": int(d_attn),
        "n_sparse_layers": n_sparse,
        "n_lightning_layers": n_light,
    }
