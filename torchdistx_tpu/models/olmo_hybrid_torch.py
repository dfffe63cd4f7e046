"""Olmo-Hybrid as a plain ``torch.nn`` module (the architecture of
allenai/Olmo-Hybrid-7B, ``model_type: olmo_hybrid``): the installed
``transformers`` has no ``olmo_hybrid``, so the paper's path
(``deferred_init`` of a torch module -> ``materialize_module_jax``) gets one
written from the published equations, as ``minicpm_sala_torch`` does for
its family.

The block is OLMo 3's (``transformers``' ``Olmo3DecoderLayer``): no norm
before a sub-block, an RMS norm after each, ``h = x +
post_attention_layernorm(mixer(x))``, ``out = h +
post_feedforward_layernorm(mlp(h))``.  The mixer is the layer's
``layer_types`` entry:

* ``full_attention``: ``self_attn.{q_proj, k_proj, v_proj, o_proj}`` with
  ``q_norm`` and ``k_norm`` over the WHOLE projection (``Olmo3Attention``'s),
  no rotary term (the row's ``rope_theta`` is null), causal softmax.
* ``linear_attention``: ``linear_attn``, FLA's ``GatedDeltaNet`` as
  ``Qwen3NextGatedDeltaNet`` computes it, with FLA's names: ``q_proj``,
  ``k_proj``, ``v_proj`` each through its own causal depthwise
  ``{q,k,v}_conv1d`` (no bias) and SiLU; q and k L2-normed per head (q by
  ``d_k^-1/2`` too); ``beta = 2 sigmoid(b_proj x)`` (``allow_neg_eigval``);
  ``g = -exp(A_log) softplus(a_proj x + dt_bias)``; the recurrence
  ``S_t = e^{g_t} (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T``,
  ``o_t = S_t^T q_t``, one position at a time; ``o_proj(o_norm(o) *
  silu(g_proj x))``, ``o_norm`` over ``d_v`` with one weight for all heads.

Names are assumed (the catalog keeps no weight names): ``model.embed_tokens``,
``model.layers.N.{self_attn | linear_attn}``,
``.post_attention_layernorm``, ``.post_feedforward_layernorm``,
``.mlp.{gate_proj, up_proj, down_proj}``, ``model.norm``, ``lm_head``.
Init: every ``Linear`` and convolution N(0, 0.02) (``transformers``'
``_init_weights``), norms ones, the embedding ``nn.Embedding``'s own N(0,
1), ``A_log = log U(0, 16)`` and ``dt_bias = 1``
(``Qwen3NextPreTrainedModel._init_weights``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import torch
import torch.nn as nn
import torch.nn.functional as F

__all__ = ["OlmoHybridConfig", "OlmoHybridForCausalLM"]

LINEAR, FULL = "linear_attention", "full_attention"


@dataclasses.dataclass
class OlmoHybridConfig:
    """The published ``config.json`` keys that shape the model."""

    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    layer_types: Optional[Tuple[str, ...]] = None
    rms_norm_eps: float = 1e-6
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02

    def __post_init__(self):
        if self.layer_types is None:
            self.layer_types = (LINEAR, LINEAR, LINEAR, FULL) * (
                self.num_hidden_layers // 4
            )
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError("layer_types must name every layer")
        if self.num_key_value_heads != self.num_attention_heads:
            raise ValueError("only num_key_value_heads == num_attention_heads is written")
        if self.linear_num_key_heads != self.linear_num_value_heads:
            raise ValueError("only as many linear key heads as value heads is written")
        if self.tie_word_embeddings:
            raise ValueError("only an untied head is written")


class RMSNorm(nn.Module):
    def __init__(self, width: int, eps: float):
        super().__init__()
        self.weight = nn.Parameter(torch.ones(width))
        self.eps = eps

    def forward(self, x):
        dtype = x.dtype
        x = x.to(torch.float32)
        x = x * torch.rsqrt(x.pow(2).mean(-1, keepdim=True) + self.eps)
        return self.weight * x.to(dtype)


def _l2norm(x, eps=1e-6):
    return x * torch.rsqrt((x * x).sum(-1, keepdim=True) + eps)


class OlmoHybridAttention(nn.Module):
    def __init__(self, config: OlmoHybridConfig):
        super().__init__()
        d, h = config.hidden_size, config.num_attention_heads
        self.n_heads, self.head_dim = h, d // h
        self.q_proj = nn.Linear(d, d, bias=False)
        self.k_proj = nn.Linear(d, d, bias=False)
        self.v_proj = nn.Linear(d, d, bias=False)
        self.o_proj = nn.Linear(d, d, bias=False)
        self.q_norm = RMSNorm(d, config.rms_norm_eps)
        self.k_norm = RMSNorm(d, config.rms_norm_eps)

    def forward(self, x):
        b, t, _ = x.shape

        def heads(a):
            return a.view(b, t, self.n_heads, self.head_dim).transpose(1, 2)

        q = heads(self.q_norm(self.q_proj(x)))
        k = heads(self.k_norm(self.k_proj(x)))
        v = heads(self.v_proj(x))
        s = (q @ k.transpose(-1, -2)) / math.sqrt(self.head_dim)
        pos = torch.arange(t)
        s = s.masked_fill(~(pos[None, :] <= pos[:, None]), float("-inf"))
        p = torch.softmax(s, dim=-1, dtype=torch.float32).to(q.dtype)
        return self.o_proj((p @ v).transpose(1, 2).reshape(b, t, -1))


class OlmoHybridGatedDeltaNet(nn.Module):
    def __init__(self, config: OlmoHybridConfig):
        super().__init__()
        d = config.hidden_size
        h = config.linear_num_key_heads
        dk, dv = config.linear_key_head_dim, config.linear_value_head_dim
        self.h, self.dk, self.dv = h, dk, dv
        self.allow_neg_eigval = config.linear_allow_neg_eigval
        self.q_proj = nn.Linear(d, h * dk, bias=False)
        self.k_proj = nn.Linear(d, h * dk, bias=False)
        self.v_proj = nn.Linear(d, h * dv, bias=False)
        self.a_proj = nn.Linear(d, h, bias=False)
        self.b_proj = nn.Linear(d, h, bias=False)
        self.g_proj = nn.Linear(d, h * dv, bias=False)
        self.o_proj = nn.Linear(h * dv, d, bias=False)
        taps = config.linear_conv_kernel_dim
        for name, width in (("q", h * dk), ("k", h * dk), ("v", h * dv)):
            setattr(self, f"{name}_conv1d", nn.Conv1d(
                width, width, taps, groups=width, padding=taps - 1, bias=False
            ))
        self.A_log = nn.Parameter(torch.log(torch.empty(h).uniform_(0, 16)))
        self.dt_bias = nn.Parameter(torch.ones(h))
        self.o_norm = RMSNorm(dv, config.rms_norm_eps)

    def forward(self, x):
        b, t, _ = x.shape

        def conv(name, u):
            u = getattr(self, f"{name}_conv1d")(u.transpose(1, 2))[..., :t]
            return F.silu(u.transpose(1, 2))

        q = conv("q", self.q_proj(x)).view(b, t, self.h, self.dk)
        k = conv("k", self.k_proj(x)).view(b, t, self.h, self.dk)
        v = conv("v", self.v_proj(x)).view(b, t, self.h, self.dv)
        q = _l2norm(q.float()) / math.sqrt(self.dk)
        k = _l2norm(k.float())
        beta = torch.sigmoid(self.b_proj(x).float())
        if self.allow_neg_eigval:
            beta = 2.0 * beta
        g = -self.A_log.float().exp() * F.softplus(
            self.a_proj(x).float() + self.dt_bias.float()
        )
        s = torch.zeros(b, self.h, self.dk, self.dv)
        out = []
        for i in range(t):
            s = s * g[:, i].exp()[..., None, None]
            kv = torch.einsum("bhk,bhkv->bhv", k[:, i], s)
            delta = (v[:, i].float() - kv) * beta[:, i, :, None]
            s = s + k[:, i, :, :, None] * delta[:, :, None, :]
            out.append(torch.einsum("bhk,bhkv->bhv", q[:, i], s))
        o = self.o_norm(torch.stack(out, 1).to(x.dtype))
        o = (o.float() * F.silu(self.g_proj(x).view(b, t, self.h, self.dv).float()))
        return self.o_proj(o.to(x.dtype).reshape(b, t, -1))


class OlmoHybridMLP(nn.Module):
    def __init__(self, config: OlmoHybridConfig):
        super().__init__()
        h, f = config.hidden_size, config.intermediate_size
        self.gate_proj = nn.Linear(h, f, bias=False)
        self.up_proj = nn.Linear(h, f, bias=False)
        self.down_proj = nn.Linear(f, h, bias=False)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class OlmoHybridDecoderLayer(nn.Module):
    def __init__(self, config: OlmoHybridConfig, layer_idx: int):
        super().__init__()
        hidden, eps = config.hidden_size, config.rms_norm_eps
        if config.layer_types[layer_idx] == FULL:
            self.self_attn = OlmoHybridAttention(config)
        else:
            self.linear_attn = OlmoHybridGatedDeltaNet(config)
        self.mlp = OlmoHybridMLP(config)
        self.post_attention_layernorm = RMSNorm(hidden, eps)
        self.post_feedforward_layernorm = RMSNorm(hidden, eps)

    def forward(self, x):
        mixer = getattr(self, "self_attn", None) or self.linear_attn
        x = x + self.post_attention_layernorm(mixer(x))
        return x + self.post_feedforward_layernorm(self.mlp(x))


class OlmoHybridModel(nn.Module):
    def __init__(self, config: OlmoHybridConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size, config.hidden_size)
        self.layers = nn.ModuleList(
            OlmoHybridDecoderLayer(config, i)
            for i in range(config.num_hidden_layers)
        )
        self.norm = RMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids)
        for layer in self.layers:
            x = layer(x)
        return self.norm(x)


class OlmoHybridForCausalLM(nn.Module):
    def __init__(self, config: OlmoHybridConfig):
        super().__init__()
        self.config = config
        self.model = OlmoHybridModel(config)
        self.lm_head = nn.Linear(config.hidden_size, config.vocab_size, bias=False)
        self.apply(self._init_weights)

    def _init_weights(self, module):
        # The embedding keeps its N(0, 1); A_log and dt_bias their own.
        if isinstance(module, (nn.Linear, nn.Conv1d)):
            module.weight.data.normal_(mean=0.0, std=self.config.initializer_range)

    def forward(self, input_ids):
        """Token ids ``(B, T)`` -> logits ``(B, T, V)``."""
        return self.lm_head(self.model(input_ids))
