"""SmallThinker as a plain ``torch.nn`` module (the architecture of
PowerInfer/SmallThinker-21BA3B-Instruct, arXiv:2507.20984): the installed
``transformers`` has no ``smallthinker``, so the paper's path
(``deferred_init`` of a torch module -> ``materialize_module_jax``) gets
one written from the published layer equations, as ``afmoe_torch`` does
for its family.

Module and parameter names follow the published checkpoint's:
``model.embed_tokens``, ``model.layers.N.{input_layernorm,
post_attention_layernorm}``, ``.self_attn.{q_proj, k_proj, v_proj,
o_proj}``, ``.block_sparse_moe.primary_router``,
``.block_sparse_moe.experts.E.{gate, up, down}``, ``model.norm``,
``lm_head``.  No bias anywhere.

The forward is the published one in plain torch: the router reads the
layer's INPUT (before ``input_layernorm``, before attention), the six
selected logits go through a softmax (``moe_primary_router_apply_softmax``
with ``norm_topk_prob``), eager attention with an explicit visibility mask
(layer ``i`` slides and ropes iff ``sliding_window_layout[i]`` /
``rope_layout[i]`` is 1), ReGLU experts, every expert on every token
weighted by a mask.  Assumed, because the catalog's ``config.json`` keeps
no such key: ``initializer_range`` 0.02, normal for every ``Linear`` (the
router among them), ones for norms, and the embedding left to
``nn.Embedding``'s own N(0, 1).  Why not 0.02 there too: an untrained
attention layer averages its window, so whatever its inputs share passes it
whole while a token's own part shrinks by the root of the window; over rows
of 0.02 that common vector outgrows the tokens by the second layer, the
router, which reads the un-normed stream, sends nine tokens in ten to the
same six experts, and which experts those are is the seed's draw.  Rows of
unit variance keep the token's own part on top, and the router spreads its
tokens as a trained one does.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import torch
import torch.nn as nn

__all__ = ["SmallThinkerConfig", "SmallThinkerForCausalLM"]


@dataclasses.dataclass
class SmallThinkerConfig:
    """The published ``config.json`` keys that shape the model."""

    vocab_size: int = 151936
    hidden_size: int = 2560
    num_hidden_layers: int = 52
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    moe_ffn_hidden_size: int = 768
    moe_num_primary_experts: int = 64
    moe_num_active_primary_experts: int = 6
    moe_primary_router_apply_softmax: bool = True
    norm_topk_prob: bool = True
    sliding_window_size: int = 4096
    # 1: the layer slides / ropes; None: every fourth layer from layer 0
    # is full attention with no position term, as published.
    sliding_window_layout: Optional[Tuple[int, ...]] = None
    rope_layout: Optional[Tuple[int, ...]] = None
    rope_theta: float = 1.5e6
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02

    def __post_init__(self):
        for key in ("sliding_window_layout", "rope_layout"):
            layout = getattr(self, key)
            if layout is None:
                layout = (int(i % 4 != 0) for i in range(self.num_hidden_layers))
            layout = tuple(int(v) for v in layout)
            setattr(self, key, layout)
            if len(layout) != self.num_hidden_layers or set(layout) - {0, 1}:
                raise ValueError(f"{key} must give 0 or 1 for every layer")
        if not (self.moe_primary_router_apply_softmax and self.norm_topk_prob):
            raise ValueError("only a softmax over the selected logits is written")
        if self.tie_word_embeddings:
            raise ValueError("only an untied head is written")


class SmallThinkerRMSNorm(nn.Module):
    def __init__(self, width: int, eps: float):
        super().__init__()
        self.weight = nn.Parameter(torch.ones(width))
        self.eps = eps

    def forward(self, x):
        dtype = x.dtype
        x = x.to(torch.float32)
        x = x * torch.rsqrt(x.pow(2).mean(-1, keepdim=True) + self.eps)
        return self.weight * x.to(dtype)


class SmallThinkerExpert(nn.Module):
    """ReGLU: ``down(relu(gate(x)) * up(x))``."""

    def __init__(self, hidden: int, width: int):
        super().__init__()
        self.gate = nn.Linear(hidden, width, bias=False)
        self.up = nn.Linear(hidden, width, bias=False)
        self.down = nn.Linear(width, hidden, bias=False)

    def forward(self, x):
        return self.down(torch.relu(self.gate(x)) * self.up(x))


class SmallThinkerMoeBlock(nn.Module):
    def __init__(self, config: SmallThinkerConfig):
        super().__init__()
        self.top_k = config.moe_num_active_primary_experts
        self.primary_router = nn.Linear(
            config.hidden_size, config.moe_num_primary_experts, bias=False
        )
        self.experts = nn.ModuleList(
            SmallThinkerExpert(config.hidden_size, config.moe_ffn_hidden_size)
            for _ in range(config.moe_num_primary_experts)
        )

    def route(self, router_input):
        """``(weights (T, k), selected (T, k))`` from the LAYER'S input:
        float32 logits, the ``k`` largest, a softmax over those."""
        logits = self.primary_router(router_input).to(torch.float32)
        top, selected = torch.topk(logits, self.top_k, dim=-1)
        return torch.softmax(top, dim=-1), selected

    def forward(self, router_input, x):
        shape = x.shape
        x = x.reshape(-1, shape[-1])
        weights, selected = self.route(router_input.reshape(-1, shape[-1]))
        out = torch.zeros_like(x, dtype=torch.float32)
        for e, expert in enumerate(self.experts):
            w_e = (weights * (selected == e)).sum(-1, keepdim=True)
            out = out + w_e * expert(x).to(torch.float32)
        return out.to(x.dtype).reshape(shape)


def _rotate_half(x):
    half = x.shape[-1] // 2
    return torch.cat([-x[..., half:], x[..., :half]], dim=-1)


class SmallThinkerAttention(nn.Module):
    def __init__(self, config: SmallThinkerConfig, layer_idx: int):
        super().__init__()
        self.config = config
        self.slides = bool(config.sliding_window_layout[layer_idx])
        self.ropes = bool(config.rope_layout[layer_idx])
        hidden, hd = config.hidden_size, config.head_dim
        n_q, n_kv = config.num_attention_heads, config.num_key_value_heads
        self.q_proj = nn.Linear(hidden, n_q * hd, bias=False)
        self.k_proj = nn.Linear(hidden, n_kv * hd, bias=False)
        self.v_proj = nn.Linear(hidden, n_kv * hd, bias=False)
        self.o_proj = nn.Linear(n_q * hd, hidden, bias=False)

    def forward(self, x):
        c = self.config
        b, t, _ = x.shape
        hd, n_q, n_kv = c.head_dim, c.num_attention_heads, c.num_key_value_heads
        q = self.q_proj(x).view(b, t, n_q, hd).transpose(1, 2)
        k = self.k_proj(x).view(b, t, n_kv, hd).transpose(1, 2)
        v = self.v_proj(x).view(b, t, n_kv, hd).transpose(1, 2)
        pos = torch.arange(t)
        visible = pos[None, :] <= pos[:, None]
        if self.ropes:
            inv = 1.0 / c.rope_theta ** (
                torch.arange(0, hd, 2, dtype=torch.float32) / hd
            )
            ang = pos.to(torch.float32)[:, None] * inv[None]
            emb = torch.cat([ang, ang], dim=-1)
            cos, sin = emb.cos().to(x.dtype), emb.sin().to(x.dtype)
            q = q * cos + _rotate_half(q) * sin
            k = k * cos + _rotate_half(k) * sin
        if self.slides:
            visible = visible & (pos[:, None] - pos[None, :] < c.sliding_window_size)
        k = k.repeat_interleave(n_q // n_kv, dim=1)
        v = v.repeat_interleave(n_q // n_kv, dim=1)
        scores = (q @ k.transpose(-1, -2)) * hd**-0.5
        scores = scores.masked_fill(~visible, float("-inf"))
        probs = torch.softmax(scores, dim=-1, dtype=torch.float32).to(q.dtype)
        out = (probs @ v).transpose(1, 2).reshape(b, t, n_q * hd)
        return self.o_proj(out)


class SmallThinkerDecoderLayer(nn.Module):
    def __init__(self, config: SmallThinkerConfig, layer_idx: int):
        super().__init__()
        hidden, eps = config.hidden_size, config.rms_norm_eps
        self.self_attn = SmallThinkerAttention(config, layer_idx)
        self.block_sparse_moe = SmallThinkerMoeBlock(config)
        self.input_layernorm = SmallThinkerRMSNorm(hidden, eps)
        self.post_attention_layernorm = SmallThinkerRMSNorm(hidden, eps)

    def forward(self, x):
        router_input = x  # before the attention's norm, before attention
        x = x + self.self_attn(self.input_layernorm(x))
        return x + self.block_sparse_moe(
            router_input, self.post_attention_layernorm(x)
        )


class SmallThinkerModel(nn.Module):
    def __init__(self, config: SmallThinkerConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size, config.hidden_size)
        self.layers = nn.ModuleList(
            SmallThinkerDecoderLayer(config, i)
            for i in range(config.num_hidden_layers)
        )
        self.norm = SmallThinkerRMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids)
        for layer in self.layers:
            x = layer(x)
        return self.norm(x)


class SmallThinkerForCausalLM(nn.Module):
    def __init__(self, config: SmallThinkerConfig):
        super().__init__()
        self.config = config
        self.model = SmallThinkerModel(config)
        self.lm_head = nn.Linear(config.hidden_size, config.vocab_size, bias=False)
        self.apply(self._init_weights)

    def _init_weights(self, module):
        std = self.config.initializer_range
        if isinstance(module, nn.Linear):  # the embedding keeps its N(0, 1)
            module.weight.data.normal_(mean=0.0, std=std)

    def forward(self, input_ids):
        """Token ids ``(B, T)`` -> logits ``(B, T, V)``."""
        return self.lm_head(self.model(input_ids))
