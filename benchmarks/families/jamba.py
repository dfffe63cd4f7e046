"""Jamba family (state-space layers with an attention layer a period, one
expert): the published ``config.json`` keys -> the Hugging Face module the
paper's path constructs, the repo's native model, and the counts from
shapes the per-layer metrics need.

The module is constructed with ``use_mamba_kernels=False`` whatever the
published key says: it names CUDA kernels, not a shape, and with it the
constructor only warns that they are absent.  And with ``pad_token_id=None``
(the class's default is 0): ``transformers`` zeroes the pad token's row of
the embedding at initialisation, the traffic draws every row, and a row of
zeros stays exactly zero through every Mamba layer before the first
attention layer (each gates its output by ``silu(z)`` with ``z = 0``), so
that seven RMS norms in a row see a zero vector and each multiplies that
position's gradient by ``eps**-0.5`` = 1,000: row 0's gradient came out
some 1e7 times a row's (PERF.md section 6, PR 31).  A checkpoint's pad row
is never an input; here no row is the pad's."""

REFERENCE = "jamba"
HF_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "hidden_act",
    "rms_norm_eps", "tie_word_embeddings", "max_position_embeddings",
    "num_experts", "num_experts_per_tok", "expert_layer_period",
    "expert_layer_offset", "attn_layer_period", "attn_layer_offset",
    "mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank",
    "mamba_conv_bias", "mamba_proj_bias", "sliding_window",
)


def hf(sizes: dict):
    from transformers import JambaConfig, JambaForCausalLM

    if sizes["num_experts"] != 1:
        raise ValueError("models/jamba.py runs one expert (a plain MLP)")
    return JambaForCausalLM, JambaConfig(
        use_mamba_kernels=False, pad_token_id=None,
        **{k: sizes[k] for k in HF_KEYS}
    )


def native(sizes: dict, dtype):
    from torchdistx_tpu.models import jamba

    return jamba, jamba.JambaConfig(
        vocab_size=sizes["vocab_size"], dim=sizes["hidden_size"],
        n_layers=sizes["num_hidden_layers"],
        attn_period=sizes["attn_layer_period"],
        attn_offset=sizes["attn_layer_offset"],
        n_heads=sizes["num_attention_heads"],
        n_kv_heads=sizes["num_key_value_heads"],
        ffn_dim=sizes["intermediate_size"], d_state=sizes["mamba_d_state"],
        d_conv=sizes["mamba_d_conv"], dt_rank=sizes["mamba_dt_rank"],
        expand=sizes["mamba_expand"], norm_eps=sizes["rms_norm_eps"],
        dtype=dtype, scan_chunk=sizes.get("scan_chunk", 128),
    )


def to_params(arrays: dict, cfg):
    from torchdistx_tpu.models import convert

    return convert.jamba_params_from_hf(arrays, cfg)


def counts(sizes: dict) -> dict:
    """From shapes.  ``matmul_params``: parameters a token multiplies: in a
    Mamba layer ``W_in``, ``W_x``, ``W_dt``, ``W_out``; in the attention
    layer ``W_q``, ``W_k``, ``W_v``, ``W_o``; every layer's feed-forward;
    the tied head (looked-up embeddings do no arithmetic).  Attention's own
    products are counted for the ATTENTION layers only: ``n_layers`` here
    is THEIR count (``shapes.train_flops_per_token`` multiplies the
    attention term by it), ``d_attn`` the heads times their width.

    NOT in ``train_mfu_pct``'s numerator: the selective scan's and the
    convolution's element-wise work (vector-unit work, about 9 operations
    on each of ``T * d_inner * d_state`` state elements a layer forward);
    ``benchlib/ssm.py`` counts the scan's BYTES for its own metric."""
    d, ffn = sizes["hidden_size"], sizes["intermediate_size"]
    inner = sizes["mamba_expand"] * d
    n, r = sizes["mamba_d_state"], sizes["mamba_dt_rank"]
    layers = sizes["num_hidden_layers"]
    n_attn = layers // sizes["attn_layer_period"]
    head_dim = d // sizes["num_attention_heads"]
    kv = sizes["num_key_value_heads"] * head_dim
    mlp = 3 * d * ffn
    mamba = d * 2 * inner + inner * (r + 2 * n) + r * inner + inner * d + mlp
    attn = 2 * d * d + 2 * d * kv + mlp
    return {
        "matmul_params": (
            (layers - n_attn) * mamba + n_attn * attn + sizes["vocab_size"] * d
        ),
        "n_layers": n_attn,
        "d_attn": d,
        "n_mamba_layers": layers - n_attn,
        "d_inner": inner,
    }
