"""GPT-2 family: the published ``config.json`` keys -> the Hugging Face
module the paper's path constructs, the repo's native model, and the
counts from shapes the per-layer metrics need."""

REFERENCE = "gpt2"
HF_KEYS = (
    "vocab_size", "n_positions", "n_embd", "n_layer", "n_head",
    "layer_norm_epsilon", "activation_function", "initializer_range",
)


def hf(sizes: dict):
    from transformers import GPT2Config, GPT2LMHeadModel

    return GPT2LMHeadModel, GPT2Config(**{k: sizes[k] for k in HF_KEYS})


def native(sizes: dict, dtype):
    from torchdistx_tpu.models import gpt2

    return gpt2, gpt2.GPT2Config(
        vocab_size=sizes["vocab_size"], dim=sizes["n_embd"],
        n_layers=sizes["n_layer"], n_heads=sizes["n_head"],
        max_seq_len=sizes["n_positions"],
        norm_eps=sizes["layer_norm_epsilon"], dtype=dtype,
    )


def to_params(arrays: dict, cfg):
    from torchdistx_tpu.models import convert

    return convert.gpt2_params_from_hf(arrays, cfg)


def counts(sizes: dict) -> dict:
    """From shapes.  ``matmul_params``: parameters inside matrix
    multiplications (QKV 3d^2, projection d^2, MLP 8d^2 per layer, and the
    tied head V*d; looked-up embeddings do no arithmetic).  ``decode_read``:
    parameters a decode step reads — every layer weight, bias and norm, the
    final norm and the tied head; of ``wpe`` only a row per slot
    (neglected).  ``kv_per_position``: cached K and V values per position."""
    d, n_layer, v = sizes["n_embd"], sizes["n_layer"], sizes["vocab_size"]
    per_layer_mm = 12 * d * d
    per_layer_rest = (3 * d + d + 4 * d + d) + 4 * d  # biases + two norms
    return {
        "matmul_params": n_layer * per_layer_mm + v * d,
        "decode_read_params": (
            n_layer * (per_layer_mm + per_layer_rest) + v * d + 2 * d
        ),
        "kv_per_position": 2 * n_layer * d,
        "n_layers": n_layer,
        "d_attn": d,
    }
