"""HF-checkpoint → native parameter bridge.

Closes the loop between the deferred-init world and the native training
stack: construct a HF model under ``deferred_init`` (zero allocation),
materialize its parameters as sharded ``jax.Array``s
(:func:`torchdistx_tpu.materialize.materialize_module_jax`), then convert
the flat ``{qualified_name: array}`` dict into the stacked-layer pytrees the
native model families (:mod:`~torchdistx_tpu.models.llama`,
:mod:`~torchdistx_tpu.models.gpt2`) train and decode with.

Layout notes:

* HF GPT-2 uses Conv1D — weights already ``(in, out)``, no transpose.
* HF Llama uses ``nn.Linear`` — weights ``(out, in)``, transposed here.
* RoPE half-split convention matches between HF Llama and
  :func:`llama._rope` (verified by the logit-equivalence tests).
* Layer stacking: per-layer leaves are stacked on a new leading axis in
  layer order, matching the ``lax.scan`` layout.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax.numpy as jnp

from . import gpt2 as gpt2_mod
from . import llama as llama_mod

__all__ = [
    "gpt2_config_from_hf",
    "llama_config_from_hf",
    "gpt2_params_from_hf",
    "llama_params_from_hf",
    "deepseek_v3_params_from_hf",
    "jamba_params_from_hf",
    "afmoe_params_from_hf",
    "afmoe_params_to_hf",
    "smallthinker_params_from_hf",
    "minicpm_sala_params_from_hf",
    "olmo_hybrid_params_from_hf",
]


def gpt2_config_from_hf(hf_config, **overrides) -> gpt2_mod.GPT2Config:
    return gpt2_mod.GPT2Config(
        vocab_size=hf_config.vocab_size,
        dim=hf_config.n_embd,
        n_layers=hf_config.n_layer,
        n_heads=hf_config.n_head,
        max_seq_len=hf_config.n_positions,
        norm_eps=hf_config.layer_norm_epsilon,
        **overrides,
    )


def llama_config_from_hf(hf_config, **overrides) -> llama_mod.LlamaConfig:
    return llama_mod.LlamaConfig(
        vocab_size=hf_config.vocab_size,
        dim=hf_config.hidden_size,
        n_layers=hf_config.num_hidden_layers,
        n_heads=hf_config.num_attention_heads,
        n_kv_heads=getattr(
            hf_config, "num_key_value_heads", hf_config.num_attention_heads
        ),
        ffn_dim=hf_config.intermediate_size,
        max_seq_len=hf_config.max_position_embeddings,
        rope_theta=getattr(hf_config, "rope_theta", 10000.0),
        norm_eps=hf_config.rms_norm_eps,
        **overrides,
    )


def _get(arrays: Dict[str, Any], name: str, *, prefixes=("", "transformer.",
                                                         "model.")):
    for p in prefixes:
        if p + name in arrays:
            return jnp.asarray(arrays[p + name])
    raise KeyError(
        f"parameter '{name}' not found (tried prefixes {list(prefixes)}); "
        f"have e.g. {sorted(arrays)[:5]}"
    )


def _stack(arrays, fmt: str, n_layers: int, *, transpose=False):
    leaves = []
    for i in range(n_layers):
        a = _get(arrays, fmt.format(i=i))
        leaves.append(a.T if transpose else a)
    return jnp.stack(leaves)


def gpt2_params_from_hf(
    arrays: Dict[str, Any], cfg: Optional[gpt2_mod.GPT2Config] = None
):
    """Flat HF GPT-2 param dict → native stacked pytree.

    ``arrays``: ``{name: array-like}`` — the output of
    ``materialize_module_jax(GPT2LMHeadModel-instance)``, a torch
    ``state_dict()`` (tensors converted via ``numpy()``), or any mix.
    """
    L = cfg.n_layers if cfg is not None else _count_layers(arrays, "h.{i}.ln_1.weight")
    return {
        "wte": {"weight": _get(arrays, "wte.weight")},
        "wpe": {"weight": _get(arrays, "wpe.weight")},
        "layers": {
            "ln_1": {
                "scale": _stack(arrays, "h.{i}.ln_1.weight", L),
                "bias": _stack(arrays, "h.{i}.ln_1.bias", L),
            },
            "attn_qkv": {
                "weight": _stack(arrays, "h.{i}.attn.c_attn.weight", L),
                "bias": _stack(arrays, "h.{i}.attn.c_attn.bias", L),
            },
            "attn_proj": {
                "weight": _stack(arrays, "h.{i}.attn.c_proj.weight", L),
                "bias": _stack(arrays, "h.{i}.attn.c_proj.bias", L),
            },
            "ln_2": {
                "scale": _stack(arrays, "h.{i}.ln_2.weight", L),
                "bias": _stack(arrays, "h.{i}.ln_2.bias", L),
            },
            "mlp_fc": {
                "weight": _stack(arrays, "h.{i}.mlp.c_fc.weight", L),
                "bias": _stack(arrays, "h.{i}.mlp.c_fc.bias", L),
            },
            "mlp_proj": {
                "weight": _stack(arrays, "h.{i}.mlp.c_proj.weight", L),
                "bias": _stack(arrays, "h.{i}.mlp.c_proj.bias", L),
            },
        },
        "ln_f": {
            "scale": _get(arrays, "ln_f.weight"),
            "bias": _get(arrays, "ln_f.bias"),
        },
    }


def llama_params_from_hf(
    arrays: Dict[str, Any], cfg: Optional[llama_mod.LlamaConfig] = None
):
    """Flat HF Llama param dict → native stacked pytree (linears
    transposed to ``(in, out)``)."""
    L = (
        cfg.n_layers
        if cfg is not None
        else _count_layers(arrays, "layers.{i}.input_layernorm.weight")
    )
    lm_head = (
        _get(arrays, "lm_head.weight")
        if any(k.endswith("lm_head.weight") for k in arrays)
        else _get(arrays, "embed_tokens.weight")
    )
    return {
        "embed": {"weight": _get(arrays, "embed_tokens.weight")},
        "layers": {
            "attn_norm": _stack(arrays, "layers.{i}.input_layernorm.weight", L),
            "wq": _stack(arrays, "layers.{i}.self_attn.q_proj.weight", L,
                         transpose=True),
            "wk": _stack(arrays, "layers.{i}.self_attn.k_proj.weight", L,
                         transpose=True),
            "wv": _stack(arrays, "layers.{i}.self_attn.v_proj.weight", L,
                         transpose=True),
            "wo": _stack(arrays, "layers.{i}.self_attn.o_proj.weight", L,
                         transpose=True),
            "mlp_norm": _stack(
                arrays, "layers.{i}.post_attention_layernorm.weight", L
            ),
            "w_gate": _stack(arrays, "layers.{i}.mlp.gate_proj.weight", L,
                             transpose=True),
            "w_up": _stack(arrays, "layers.{i}.mlp.up_proj.weight", L,
                           transpose=True),
            "w_down": _stack(arrays, "layers.{i}.mlp.down_proj.weight", L,
                             transpose=True),
        },
        "norm": {"weight": _get(arrays, "norm.weight")},
        "lm_head": {"weight": lm_head.T},
    }


def deepseek_v3_params_from_hf(arrays: Dict[str, Any], cfg):
    """Flat HF DeepSeek-V3 param dict -> the two stacks of
    :mod:`~torchdistx_tpu.models.deepseek_v3` (linears transposed to
    ``(in, out)``; the experts HELD, ``experts.0 .. experts.{held-1}`` of
    the module as it stands after the others were dropped, stacked on an
    expert axis; ``e_score_correction_bias`` as ``router_bias``)."""
    attn = {
        "attn_norm": "input_layernorm", "wq": "self_attn.q_proj",
        "wkv_a": "self_attn.kv_a_proj_with_mqa",
        "kv_norm": "self_attn.kv_a_layernorm", "wkv_b": "self_attn.kv_b_proj",
        "wo": "self_attn.o_proj", "mlp_norm": "post_attention_layernorm",
    }
    mlp = {"gate": "gate_proj", "up": "up_proj", "down": "down_proj"}

    def leaf(i, name):
        a = _get(arrays, f"layers.{i}.{name}.weight")
        return a.T if a.ndim == 2 else a

    def stack(layers, table):
        return {
            k: jnp.stack([leaf(i, name) for i in layers])
            for k, name in table.items()
        }

    dense = range(cfg.n_dense_layers)
    moe = range(cfg.n_dense_layers, cfg.n_layers)
    return {
        "embed": {"weight": _get(arrays, "embed_tokens.weight")},
        "dense_layers": {
            **stack(dense, attn),
            **stack(dense, {f"w_{k}": f"mlp.{v}" for k, v in mlp.items()}),
        },
        "moe_layers": {
            **stack(moe, attn),
            **stack(moe, {"router": "mlp.gate"}),
            "router_bias": jnp.stack([
                _get(arrays, f"layers.{i}.mlp.gate.e_score_correction_bias")
                for i in moe
            ]),
            **{
                f"e_{k}": jnp.stack([
                    jnp.stack([
                        leaf(i, f"mlp.experts.{e}.{v}")
                        for e in range(cfg.held)
                    ])
                    for i in moe
                ])
                for k, v in mlp.items()
            },
            **stack(
                moe, {f"s_{k}": f"mlp.shared_experts.{v}" for k, v in mlp.items()}
            ),
        },
        "norm": {"weight": _get(arrays, "norm.weight")},
        "lm_head": {"weight": _get(arrays, "lm_head.weight").T},
    }


def jamba_params_from_hf(arrays: Dict[str, Any], cfg):
    """Flat HF Jamba param dict (one expert: ``feed_forward`` is a plain
    MLP) -> the period stacks of :mod:`~torchdistx_tpu.models.jamba`.
    Linears are transposed to ``(in, out)``; the depthwise convolution's
    ``(C, 1, K)`` weight becomes taps-major ``(K, C)``; ``A_log`` and ``D``
    keep their shapes.  The head is tied: ``lm_head.weight`` is the
    embedding and is not read."""
    mlp = {
        "mlp_norm": "pre_ff_layernorm", "w_gate": "feed_forward.gate_proj",
        "w_up": "feed_forward.up_proj", "w_down": "feed_forward.down_proj",
    }
    mamba = {
        "mixer_norm": "input_layernorm", "w_in": "mamba.in_proj",
        "w_x": "mamba.x_proj", "dt_norm": "mamba.dt_layernorm",
        "b_norm": "mamba.b_layernorm", "c_norm": "mamba.c_layernorm",
        "w_dt": "mamba.dt_proj", "w_out": "mamba.out_proj", **mlp,
    }
    attn = {
        "mixer_norm": "input_layernorm", "wq": "self_attn.q_proj",
        "wk": "self_attn.k_proj", "wv": "self_attn.v_proj",
        "wo": "self_attn.o_proj", **mlp,
    }

    def layer(i, table):
        out = {}
        for k, name in table.items():
            a = _get(arrays, f"layers.{i}.{name}.weight")
            out[k] = a.T if a.ndim == 2 else a
        if table is mamba:
            pre = f"layers.{i}.mamba."
            out["conv_w"] = _get(arrays, pre + "conv1d.weight")[:, 0, :].T
            out["conv_b"] = _get(arrays, pre + "conv1d.bias")
            out["b_dt"] = _get(arrays, pre + "dt_proj.bias")
            out["a_log"] = _get(arrays, pre + "A_log")
            out["d"] = _get(arrays, pre + "D")
        return out

    def stack(trees):
        return {k: jnp.stack([t[k] for t in trees]) for k in trees[0]}

    period, offset = cfg.attn_period, cfg.attn_offset
    starts = range(0, cfg.n_layers, period)
    periods = {"attn": stack([layer(s + offset, attn) for s in starts])}
    for name, first, n in (
        ("mamba_a", 0, offset), ("mamba_b", offset + 1, period - offset - 1),
    ):
        if n:
            periods[name] = stack([
                stack([layer(s + first + j, mamba) for j in range(n)])
                for s in starts
            ])
    return {
        "embed": {"weight": _get(arrays, "embed_tokens.weight")},
        "periods": periods,
        "norm": {"weight": _get(arrays, "final_layernorm.weight")},
    }


# AFMoE: leaf of the stacked layout -> published name inside a layer.
_AFMOE_ATTN = {
    "attn_norm": "input_layernorm", "wq": "self_attn.q_proj",
    "wk": "self_attn.k_proj", "wv": "self_attn.v_proj",
    "wg": "self_attn.gate_proj", "wo": "self_attn.o_proj",
    "q_norm": "self_attn.q_norm", "k_norm": "self_attn.k_norm",
    "post_attn_norm": "post_attention_layernorm",
    "mlp_norm": "pre_mlp_layernorm", "post_mlp_norm": "post_mlp_layernorm",
}
_AFMOE_MLP = {"gate": "gate_proj", "up": "up_proj", "down": "down_proj"}
_AFMOE_DENSE = {
    **_AFMOE_ATTN, **{f"w_{k}": f"mlp.{v}" for k, v in _AFMOE_MLP.items()},
}
_AFMOE_MOE = {
    **_AFMOE_ATTN, "router": "mlp.router.gate",
    **{f"s_{k}": f"mlp.shared_experts.{v}" for k, v in _AFMOE_MLP.items()},
}


def _layer_leaf(arrays, i, name):
    """``layers.{i}.{name}.weight``, a linear transposed to ``(in, out)``."""
    a = _get(arrays, f"layers.{i}.{name}.weight")
    return a.T if a.ndim == 2 else a


def _stack_layers(arrays, layers, table):
    """``{leaf: (L, ...)}`` of the ``layers`` for a ``{leaf: published
    name}`` table."""
    return {
        k: jnp.stack([_layer_leaf(arrays, i, name) for i in layers])
        for k, name in table.items()
    }


def _stack_experts(arrays, layers, held, experts, table):
    """``{e_<leaf>: (L, Eh, ...)}``: the ``held`` experts ``{experts}.0 ..
    {experts}.{held-1}`` of each of ``layers``, as the module stands after
    the others were dropped."""
    return {
        f"e_{k}": jnp.stack([
            jnp.stack([
                _layer_leaf(arrays, i, f"{experts}.{e}.{name}")
                for e in range(held)
            ])
            for i in layers
        ])
        for k, name in table.items()
    }


def afmoe_params_from_hf(arrays: Dict[str, Any], cfg):
    """Flat AFMoE param dict (the published names, as
    :mod:`~torchdistx_tpu.models.afmoe_torch` has them) -> the two stacks
    of :mod:`~torchdistx_tpu.models.afmoe` (linears transposed to ``(in,
    out)``; the experts HELD, ``experts.0 .. experts.{held-1}`` of the
    module as it stands after the others were dropped, stacked on an expert
    axis; ``expert_bias`` as ``router_bias``)."""

    dense = range(cfg.n_dense_layers)
    moe = range(cfg.n_dense_layers, cfg.n_layers)
    dtype = _get(arrays, "norm.weight").dtype
    return {
        "embed": {"weight": _get(arrays, "embed_tokens.weight")},
        "dense_layers": _stack_layers(arrays, dense, _AFMOE_DENSE),
        "moe_layers": {
            **_stack_layers(arrays, moe, _AFMOE_MOE),
            "router_bias": jnp.stack([
                _get(arrays, f"layers.{i}.mlp.expert_bias").astype(dtype)
                for i in moe
            ]),
            **_stack_experts(arrays, moe, cfg.held, "mlp.experts", _AFMOE_MLP),
        },
        "norm": {"weight": _get(arrays, "norm.weight")},
        "lm_head": {"weight": _get(arrays, "lm_head.weight").T},
    }


def afmoe_params_to_hf(params, cfg) -> Dict[str, Any]:
    """The inverse of :func:`afmoe_params_from_hf`: the stacked layout ->
    ``{published name: array}`` (linears back to ``(out, in)``), the held
    experts numbered from 0 as in the module after the drop.  An expert
    that is not held gets no key."""
    out = {
        "model.embed_tokens.weight": params["embed"]["weight"],
        "model.norm.weight": params["norm"]["weight"],
        "lm_head.weight": params["lm_head"]["weight"].T,
    }

    def put(name, a):
        out[f"model.layers.{name}.weight"] = a.T if a.ndim == 2 else a

    for j in range(cfg.n_dense_layers):
        for k, name in _AFMOE_DENSE.items():
            put(f"{j}.{name}", params["dense_layers"][k][j])
    for j in range(cfg.n_moe_layers):
        i, lp = cfg.n_dense_layers + j, params["moe_layers"]
        for k, name in _AFMOE_MOE.items():
            put(f"{i}.{name}", lp[k][j])
        out[f"model.layers.{i}.mlp.expert_bias"] = lp["router_bias"][j]
        for k, v in _AFMOE_MLP.items():
            for e in range(cfg.held):
                put(f"{i}.mlp.experts.{e}.{v}", lp[f"e_{k}"][j, e])
    return out


# SmallThinker: leaf of the stacked layout -> published name in a layer.
_SMALLTHINKER_LAYER = {
    "attn_norm": "input_layernorm", "mlp_norm": "post_attention_layernorm",
    "wq": "self_attn.q_proj", "wk": "self_attn.k_proj",
    "wv": "self_attn.v_proj", "wo": "self_attn.o_proj",
    "router": "block_sparse_moe.primary_router",
}


def smallthinker_params_from_hf(arrays: Dict[str, Any], cfg):
    """Flat SmallThinker param dict (the published names, as
    :mod:`~torchdistx_tpu.models.smallthinker_torch` has them) -> the one
    stack of :mod:`~torchdistx_tpu.models.smallthinker` beside its empty
    ``dense_layers`` (linears transposed to ``(in, out)``; the experts
    HELD, ``experts.0 .. experts.{held-1}`` of the module as it stands
    after the others were dropped, stacked on an expert axis)."""

    layers = range(cfg.n_layers)
    return {
        "embed": {"weight": _get(arrays, "embed_tokens.weight")},
        "dense_layers": {},
        "moe_layers": {
            **_stack_layers(arrays, layers, _SMALLTHINKER_LAYER),
            **_stack_experts(
                arrays, layers, cfg.held, "block_sparse_moe.experts",
                {k: k for k in ("gate", "up", "down")},
            ),
        },
        "norm": {"weight": _get(arrays, "norm.weight")},
        "lm_head": {"weight": _get(arrays, "lm_head.weight").T},
    }


# MiniCPM-SALA: leaf of a kind's stack -> name in a layer.
_MINICPM_SALA_LAYER = {
    "attn_norm": "input_layernorm", "mlp_norm": "post_attention_layernorm",
    "wq": "self_attn.q_proj", "wk": "self_attn.k_proj",
    "wv": "self_attn.v_proj", "wg": "self_attn.o_gate",
    "wo": "self_attn.o_proj", "q_norm": "self_attn.q_norm",
    "k_norm": "self_attn.k_norm", "w_gate": "mlp.gate_proj",
    "w_up": "mlp.up_proj", "w_down": "mlp.down_proj",
}


def minicpm_sala_params_from_hf(arrays: Dict[str, Any], cfg):
    """Flat MiniCPM-SALA param dict (the names of
    :mod:`~torchdistx_tpu.models.minicpm_sala_torch`) -> the two stacks of
    :mod:`~torchdistx_tpu.models.minicpm_sala`, each in layer order over
    the layers of its kind (linears transposed to ``(in, out)``)."""
    from .minicpm_sala import LIGHTNING, SPARSE

    def of(kind):
        return [i for i, k in enumerate(cfg.mixer_types) if k == kind]

    lightning = dict(_MINICPM_SALA_LAYER, o_norm="self_attn.o_norm")
    return {
        "embed": {"weight": _get(arrays, "embed_tokens.weight")},
        "sparse_layers": _stack_layers(arrays, of(SPARSE), _MINICPM_SALA_LAYER),
        "lightning_layers": _stack_layers(arrays, of(LIGHTNING), lightning),
        "norm": {"weight": _get(arrays, "norm.weight")},
        "lm_head": {"weight": _get(arrays, "lm_head.weight").T},
    }


# Olmo-Hybrid: leaf of a kind's stack -> name in a layer.
_OLMO_HYBRID_BLOCK = {
    "post_attn_norm": "post_attention_layernorm",
    "post_ff_norm": "post_feedforward_layernorm", "w_gate": "mlp.gate_proj",
    "w_up": "mlp.up_proj", "w_down": "mlp.down_proj",
}
_OLMO_HYBRID_FULL = {
    **_OLMO_HYBRID_BLOCK, "wq": "self_attn.q_proj", "wk": "self_attn.k_proj",
    "wv": "self_attn.v_proj", "wo": "self_attn.o_proj",
    "q_norm": "self_attn.q_norm", "k_norm": "self_attn.k_norm",
}
_OLMO_HYBRID_LINEAR = {
    **_OLMO_HYBRID_BLOCK, "wq": "linear_attn.q_proj", "wk": "linear_attn.k_proj",
    "wv": "linear_attn.v_proj", "wz": "linear_attn.g_proj",
    "wb": "linear_attn.b_proj", "wa": "linear_attn.a_proj",
    "wo": "linear_attn.o_proj", "o_norm": "linear_attn.o_norm",
}


def olmo_hybrid_params_from_hf(arrays: Dict[str, Any], cfg):
    """Flat Olmo-Hybrid param dict (the names of
    :mod:`~torchdistx_tpu.models.olmo_hybrid_torch`) -> the two stacks of
    :mod:`~torchdistx_tpu.models.olmo_hybrid`, each in layer order over the
    layers of its kind (linears transposed to ``(in, out)``, a depthwise
    convolution's ``(C, 1, K)`` to its taps ``(K, C)``)."""
    from .olmo_hybrid import FULL, LINEAR

    def of(kind):
        return [i for i, k in enumerate(cfg.layer_types) if k == kind]

    linear = of(LINEAR)
    stack = _stack_layers(arrays, linear, _OLMO_HYBRID_LINEAR)
    for name in ("q", "k", "v"):
        stack[f"conv_{name}"] = jnp.stack([
            _get(arrays, f"layers.{i}.linear_attn.{name}_conv1d.weight")[:, 0, :].T
            for i in linear
        ])
    for name in ("A_log", "dt_bias"):
        stack[name] = jnp.stack([
            _get(arrays, f"layers.{i}.linear_attn.{name}") for i in linear
        ])
    return {
        "embed": {"weight": _get(arrays, "embed_tokens.weight")},
        "linear_layers": stack,
        "full_layers": _stack_layers(arrays, of(FULL), _OLMO_HYBRID_FULL),
        "norm": {"weight": _get(arrays, "norm.weight")},
        "lm_head": {"weight": _get(arrays, "lm_head.weight").T},
    }


def _count_layers(arrays, fmt: str) -> int:
    i = 0
    while True:
        name = fmt.format(i=i)
        if not any(k.endswith(name) for k in arrays):
            return i
        i += 1
