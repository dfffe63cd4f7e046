"""Llama/Mistral family (dense, GQA, rotary, SwiGLU, untied head): the
published ``config.json`` keys -> the Hugging Face module the paper's path
constructs, the repo's native model (``models/llama.py``), and the counts
from shapes the per-layer metrics need."""

REFERENCE = "llama"
HF_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "hidden_act",
    "max_position_embeddings", "initializer_range", "rms_norm_eps",
    "rope_theta", "sliding_window", "tie_word_embeddings",
    "attention_dropout",
)


def hf(sizes: dict):
    from transformers import MistralConfig, MistralForCausalLM

    if sizes["sliding_window"] is not None or sizes["tie_word_embeddings"]:
        raise ValueError("models/llama.py has no sliding window or tied head")
    return MistralForCausalLM, MistralConfig(
        **{k: sizes[k] for k in HF_KEYS}
    )


def native(sizes: dict, dtype):
    from torchdistx_tpu.models import llama

    if sizes["hidden_size"] % sizes["num_attention_heads"]:
        raise ValueError("head size must be hidden_size / heads")
    return llama, llama.LlamaConfig(
        vocab_size=sizes["vocab_size"], dim=sizes["hidden_size"],
        n_layers=sizes["num_hidden_layers"],
        n_heads=sizes["num_attention_heads"],
        n_kv_heads=sizes["num_key_value_heads"],
        ffn_dim=sizes["intermediate_size"],
        max_seq_len=sizes["max_position_embeddings"],
        rope_theta=float(sizes["rope_theta"]),
        norm_eps=sizes["rms_norm_eps"], dtype=dtype,
    )


def to_params(arrays: dict, cfg):
    from torchdistx_tpu.models import convert

    return convert.llama_params_from_hf(arrays, cfg)


def counts(sizes: dict) -> dict:
    """From shapes (see ``families/gpt2.py`` for what each count means).
    Per layer: Q and O ``d*d`` each, K and V ``d*kv`` each, gate, up and
    down ``d*f`` each, two norms.  The untied head ``V*d`` is a matmul; of
    the embedding a decode step reads a row per slot (neglected)."""
    d, f = sizes["hidden_size"], sizes["intermediate_size"]
    n_layer, v = sizes["num_hidden_layers"], sizes["vocab_size"]
    head_dim = d // sizes["num_attention_heads"]
    kv = sizes["num_key_value_heads"] * head_dim
    per_layer_mm = 2 * d * d + 2 * d * kv + 3 * d * f
    return {
        "matmul_params": n_layer * per_layer_mm + v * d,
        "decode_read_params": n_layer * (per_layer_mm + 2 * d) + v * d + d,
        "kv_per_position": 2 * n_layer * kv,
        "n_layers": n_layer,
        "d_attn": d,
    }
