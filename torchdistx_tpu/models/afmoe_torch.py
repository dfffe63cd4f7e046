"""AFMoE as a plain ``torch.nn`` module (``model_type: afmoe``, the
architecture of arcee-ai/Trinity-Mini): the installed ``transformers`` has
no ``afmoe``, so the paper's path (``deferred_init`` of a torch module ->
``materialize_module_jax``) gets one written from the published
``modeling_afmoe.py``'s equations, as ``resnet_torch`` stands in for
torchvision.

Module and parameter names are the published ones, so the published
checkpoint's keys would load: ``model.embed_tokens``, ``model.layers.N.
{input_layernorm, post_attention_layernorm, pre_mlp_layernorm,
post_mlp_layernorm}``, ``.self_attn.{q_proj, k_proj, v_proj, o_proj,
gate_proj, q_norm, k_norm}``, ``.mlp.{gate_proj, up_proj, down_proj}`` in a
dense layer, ``.mlp.router.gate``, ``.mlp.expert_bias``,
``.mlp.shared_experts.{...}``, ``.mlp.experts.E.{...}`` in an expert layer,
``model.norm``, ``lm_head``.  No bias anywhere.

The forward is the published one in plain torch (eager attention with an
explicit visibility mask, every expert on every token weighted by a mask):
``tests/test_afmoe.py`` holds the benchmark's ``jax.numpy`` reference
against it.  Assumed, because the catalog's ``config.json`` keeps no such
key: ``initializer_range`` 0.02, normal for every ``Linear`` (the router's
``gate`` among them) and the embedding, ones for norms, zeros for
``expert_bias``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import torch
import torch.nn as nn
import torch.nn.functional as F

__all__ = ["AfmoeConfig", "AfmoeForCausalLM"]


@dataclasses.dataclass
class AfmoeConfig:
    """The published ``config.json`` keys that shape the model."""

    vocab_size: int = 200192
    hidden_size: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 32
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    route_norm: bool = True
    route_scale: float = 2.826
    score_func: str = "sigmoid"
    sliding_window: int = 2048
    global_attn_every_n_layers: int = 4
    layer_types: Optional[Tuple[str, ...]] = None
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    mup_enabled: bool = True
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02

    def __post_init__(self):
        if self.layer_types is None:
            every = self.global_attn_every_n_layers
            self.layer_types = tuple(
                "full_attention" if (i + 1) % every == 0 else "sliding_attention"
                for i in range(self.num_hidden_layers)
            )
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError("layer_types must name every layer")
        if self.score_func != "sigmoid" or self.tie_word_embeddings:
            raise ValueError("only sigmoid scores and an untied head are written")


class AfmoeRMSNorm(nn.Module):
    def __init__(self, width: int, eps: float):
        super().__init__()
        self.weight = nn.Parameter(torch.ones(width))
        self.eps = eps

    def forward(self, x):
        dtype = x.dtype
        x = x.to(torch.float32)
        x = x * torch.rsqrt(x.pow(2).mean(-1, keepdim=True) + self.eps)
        return self.weight * x.to(dtype)


class AfmoeMLP(nn.Module):
    def __init__(self, hidden: int, width: int):
        super().__init__()
        self.gate_proj = nn.Linear(hidden, width, bias=False)
        self.up_proj = nn.Linear(hidden, width, bias=False)
        self.down_proj = nn.Linear(width, hidden, bias=False)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class AfmoeTokenChoiceRouter(nn.Module):
    def __init__(self, config: AfmoeConfig):
        super().__init__()
        self.config = config
        self.gate = nn.Linear(config.hidden_size, config.num_experts, bias=False)

    def forward(self, x, expert_bias):
        """``(weights (T, k), selected (T, k))``: float32 sigmoid scores,
        selection on score + bias, weights normalised and scaled."""
        scores = torch.sigmoid(self.gate(x).to(torch.float32))
        _, selected = torch.topk(
            scores + expert_bias, self.config.num_experts_per_tok, dim=-1
        )
        weights = scores.gather(-1, selected)
        if self.config.route_norm:
            weights = weights / (weights.sum(-1, keepdim=True) + 1e-20)
        return weights * self.config.route_scale, selected


class AfmoeMoE(nn.Module):
    def __init__(self, config: AfmoeConfig):
        super().__init__()
        hidden, width = config.hidden_size, config.moe_intermediate_size
        self.router = AfmoeTokenChoiceRouter(config)
        self.shared_experts = AfmoeMLP(hidden, width * config.num_shared_experts)
        self.experts = nn.ModuleList(
            AfmoeMLP(hidden, width) for _ in range(config.num_experts)
        )
        # Moved by expert load in the training framework, not by a gradient.
        self.expert_bias = nn.Parameter(
            torch.zeros(config.num_experts, dtype=torch.float32),
            requires_grad=False,
        )

    def forward(self, x):
        shape = x.shape
        x = x.reshape(-1, shape[-1])
        weights, selected = self.router(x, self.expert_bias)
        out = self.shared_experts(x).to(torch.float32)
        for e, expert in enumerate(self.experts):
            w_e = (weights * (selected == e)).sum(-1, keepdim=True)
            out = out + w_e * expert(x).to(torch.float32)
        return out.to(x.dtype).reshape(shape)


def _rotate_half(x):
    half = x.shape[-1] // 2
    return torch.cat([-x[..., half:], x[..., :half]], dim=-1)


class AfmoeAttention(nn.Module):
    def __init__(self, config: AfmoeConfig, layer_idx: int):
        super().__init__()
        self.config = config
        self.is_local = config.layer_types[layer_idx] == "sliding_attention"
        hidden, hd = config.hidden_size, config.head_dim
        n_q, n_kv = config.num_attention_heads, config.num_key_value_heads
        self.q_proj = nn.Linear(hidden, n_q * hd, bias=False)
        self.k_proj = nn.Linear(hidden, n_kv * hd, bias=False)
        self.v_proj = nn.Linear(hidden, n_kv * hd, bias=False)
        self.o_proj = nn.Linear(n_q * hd, hidden, bias=False)
        self.gate_proj = nn.Linear(hidden, n_q * hd, bias=False)
        self.q_norm = AfmoeRMSNorm(hd, config.rms_norm_eps)
        self.k_norm = AfmoeRMSNorm(hd, config.rms_norm_eps)

    def forward(self, x):
        c = self.config
        b, t, _ = x.shape
        hd, n_q, n_kv = c.head_dim, c.num_attention_heads, c.num_key_value_heads
        q = self.q_norm(self.q_proj(x).view(b, t, n_q, hd)).transpose(1, 2)
        k = self.k_norm(self.k_proj(x).view(b, t, n_kv, hd)).transpose(1, 2)
        v = self.v_proj(x).view(b, t, n_kv, hd).transpose(1, 2)
        gate = self.gate_proj(x)
        pos = torch.arange(t)
        visible = pos[None, :] <= pos[:, None]
        if self.is_local:
            inv = 1.0 / c.rope_theta ** (
                torch.arange(0, hd, 2, dtype=torch.float32) / hd
            )
            ang = pos.to(torch.float32)[:, None] * inv[None]
            emb = torch.cat([ang, ang], dim=-1)
            cos, sin = emb.cos().to(x.dtype), emb.sin().to(x.dtype)
            q = q * cos + _rotate_half(q) * sin
            k = k * cos + _rotate_half(k) * sin
            visible = visible & (pos[:, None] - pos[None, :] < c.sliding_window)
        k = k.repeat_interleave(n_q // n_kv, dim=1)
        v = v.repeat_interleave(n_q // n_kv, dim=1)
        scores = (q @ k.transpose(-1, -2)) * hd**-0.5
        scores = scores.masked_fill(~visible, float("-inf"))
        probs = torch.softmax(scores, dim=-1, dtype=torch.float32).to(q.dtype)
        out = (probs @ v).transpose(1, 2).reshape(b, t, n_q * hd)
        return self.o_proj(out * torch.sigmoid(gate))


class AfmoeDecoderLayer(nn.Module):
    def __init__(self, config: AfmoeConfig, layer_idx: int):
        super().__init__()
        hidden, eps = config.hidden_size, config.rms_norm_eps
        self.self_attn = AfmoeAttention(config, layer_idx)
        if layer_idx < config.num_dense_layers:
            self.mlp = AfmoeMLP(hidden, config.intermediate_size)
        else:
            self.mlp = AfmoeMoE(config)
        self.input_layernorm = AfmoeRMSNorm(hidden, eps)
        self.post_attention_layernorm = AfmoeRMSNorm(hidden, eps)
        self.pre_mlp_layernorm = AfmoeRMSNorm(hidden, eps)
        self.post_mlp_layernorm = AfmoeRMSNorm(hidden, eps)

    def forward(self, x):
        x = x + self.post_attention_layernorm(
            self.self_attn(self.input_layernorm(x))
        )
        return x + self.post_mlp_layernorm(self.mlp(self.pre_mlp_layernorm(x)))


class AfmoeModel(nn.Module):
    def __init__(self, config: AfmoeConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size, config.hidden_size)
        self.layers = nn.ModuleList(
            AfmoeDecoderLayer(config, i) for i in range(config.num_hidden_layers)
        )
        self.norm = AfmoeRMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids)
        if self.config.mup_enabled:
            x = x * (self.config.hidden_size**0.5)
        for layer in self.layers:
            x = layer(x)
        return self.norm(x)


class AfmoeForCausalLM(nn.Module):
    def __init__(self, config: AfmoeConfig):
        super().__init__()
        self.config = config
        self.model = AfmoeModel(config)
        self.lm_head = nn.Linear(config.hidden_size, config.vocab_size, bias=False)
        self.apply(self._init_weights)

    def _init_weights(self, module):
        std = self.config.initializer_range
        if isinstance(module, (nn.Linear, nn.Embedding)):
            module.weight.data.normal_(mean=0.0, std=std)

    def forward(self, input_ids):
        """Token ids ``(B, T)`` -> logits ``(B, T, V)``."""
        return self.lm_head(self.model(input_ids))
