"""MiniCPM-SALA as a plain ``torch.nn`` module (the architecture of
openbmb/MiniCPM-SALA, ``model_type: minicpm_sala``): the installed
``transformers`` has no ``minicpm_sala``, so the paper's path
(``deferred_init`` of a torch module -> ``materialize_module_jax``) gets one
written from the published equations, as ``smallthinker_torch`` does for
its family.

Module and parameter names follow MiniCPM's checkpoints (assumed: the
catalog keeps no weight names): ``model.embed_tokens``,
``model.layers.N.{input_layernorm, post_attention_layernorm}``,
``.self_attn.{q_proj, k_proj, v_proj, o_proj, o_gate, q_norm, k_norm}`` and,
in a ``lightning-attn`` layer, ``.self_attn.o_norm``;
``.mlp.{gate_proj, up_proj, down_proj}``, ``model.norm``, ``lm_head``.  No
bias anywhere.

The forward is the published one in plain torch, dense and explicit: the
sparse layer builds each (token, group)'s choice of blocks (compressed
keys, the group's summed softmax, the block max, block 0 and the local
window forced, the best of the rest, ties to the earlier block) and masks
a full attention with it; above ``dense_len`` only, as published.  The
lightning layer is the quadratic form ``sum_{s <= t} lambda^(t-s) (q_t .
k_s / sqrt(D)) v_s`` under the whole ``(T, T)`` decay mask.  Assumed,
because the catalog's ``config.json`` keeps no such key: every ``Linear``
N(0, 0.02), norms ones, the embedding left to ``nn.Embedding``'s own N(0,
1) (``scale_emb`` 12 then makes the stream's rows 12 wide, and a block's
update of 1.4 / sqrt(32) x its output moves it by little: the token's own
part stays on top through the period).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import torch
import torch.nn as nn

__all__ = ["MiniCPMSALAConfig", "MiniCPMSALAForCausalLM"]

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


@dataclasses.dataclass
class MiniCPMSALAConfig:
    """The published ``config.json`` keys that shape the model, and
    ``sparse_config`` (MiniCPM4-8B's values: the row gives none)."""

    vocab_size: int = 73448
    hidden_size: int = 4096
    intermediate_size: int = 16384
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    lightning_nh: int = 32
    lightning_nkv: int = 32
    lightning_head_dim: int = 128
    mixer_types: Optional[Tuple[str, ...]] = None
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    # the depth the residual scale reads: the published 32
    residual_layers: int = 32
    dim_model_base: int = 256
    tie_word_embeddings: bool = False
    initializer_range: float = 0.02
    sparse_kernel_size: int = 32
    sparse_kernel_stride: int = 16
    sparse_init_blocks: int = 1
    sparse_block_size: int = 64
    sparse_window_size: int = 2048
    sparse_topk: int = 64
    sparse_dense_len: int = 8192

    def __post_init__(self):
        if self.mixer_types is None:
            from .minicpm_sala import PUBLISHED_MIXERS

            self.mixer_types = PUBLISHED_MIXERS
        self.mixer_types = tuple(self.mixer_types)
        if len(self.mixer_types) != self.num_hidden_layers:
            raise ValueError("mixer_types must name every layer")
        if self.lightning_nkv != self.lightning_nh:
            raise ValueError("only lightning_nkv == lightning_nh is written")
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


def _rotate_half(x):
    half = x.shape[-1] // 2
    return torch.cat([-x[..., half:], x[..., :half]], dim=-1)


def _rope(x, theta):
    """``x (B, H, T, D)``: the half-split rotation over all of ``D``."""
    t, d = x.shape[2], x.shape[-1]
    inv = 1.0 / theta ** (torch.arange(0, d, 2, dtype=torch.float32) / d)
    ang = torch.arange(t, dtype=torch.float32)[:, None] * inv[None]
    emb = torch.cat([ang, ang], dim=-1)
    return x * emb.cos().to(x.dtype) + _rotate_half(x) * emb.sin().to(x.dtype)


def sparse_mask(q, k, c: MiniCPMSALAConfig):
    """``(B, Hkv, T, T)`` bool: key ``j`` is kept for query ``t`` of the
    group.  ``q (B, H, T, D)``, ``k (B, Hkv, T, D)``."""
    b, h, t, d = q.shape
    hkv = k.shape[1]
    ks, st, bs = c.sparse_kernel_size, c.sparse_kernel_stride, c.sparse_block_size
    nc, nb = (t - ks) // st + 1, t // bs
    kc = torch.stack([k[:, :, j * st: j * st + ks].mean(2) for j in range(nc)], 2)
    pos = torch.arange(t)
    win = torch.arange(nc)
    visible = win[None, :] * st + ks <= pos[:, None] + 1  # (T, NC)
    qg = q.reshape(b, hkv, h // hkv, t, d)
    s = torch.einsum("bhgtd,bhjd->bhgtj", qg, kc) / math.sqrt(d)
    s = s.masked_fill(~visible, float("-inf"))
    p = torch.softmax(s, dim=-1).nan_to_num(0.0).sum(2)  # (B, Hkv, T, NC)
    blk = torch.arange(nb)
    overlap = (win[:, None] * st < (blk[None, :] + 1) * bs) & (
        win[:, None] * st + ks > blk[None, :] * bs
    )  # (NC, NB)
    score = p[..., :, None].expand(*p.shape, nb).masked_fill(
        ~(overlap & visible[:, :, None])[None, None], float("-inf")
    ).amax(-2)  # (B, Hkv, T, NB)
    causal = blk[None, :] * bs <= pos[:, None]
    local = (blk[None, :] + 1) * bs - 1 >= pos[:, None] - c.sparse_window_size + 1
    forced = causal & (local | (blk[None, :] < c.sparse_init_blocks))
    key = torch.where(
        forced, torch.tensor(float("inf")),
        torch.where(causal, score.clamp(min=-1e30), torch.tensor(float("-inf"))),
    )
    order = torch.sort(key, dim=-1, descending=True, stable=True).indices
    top = order[..., : c.sparse_topk]
    chosen = torch.zeros_like(key, dtype=torch.bool).scatter(-1, top, True)
    chosen &= key > float("-inf")
    keys = chosen.repeat_interleave(bs, dim=-1)  # (B, Hkv, T, T)
    return keys & (pos[None, :] <= pos[:, None])


def lightning_decays(n_heads: int):
    return [-(2.0 ** (-8.0 * (h + 1) / n_heads)) for h in range(n_heads)]


class MiniCPMSALAAttention(nn.Module):
    def __init__(self, config: MiniCPMSALAConfig, layer_idx: int):
        super().__init__()
        self.config = config
        self.kind = config.mixer_types[layer_idx]
        hidden = config.hidden_size
        if self.kind == SPARSE:
            n_q, n_kv = config.num_attention_heads, config.num_key_value_heads
            hd = config.head_dim
        else:
            n_q = n_kv = config.lightning_nh
            hd = config.lightning_head_dim
            self.o_norm = RMSNorm(n_q * hd, config.rms_norm_eps)
        self.n_q, self.n_kv, self.hd = n_q, n_kv, hd
        self.q_proj = nn.Linear(hidden, n_q * hd, bias=False)
        self.k_proj = nn.Linear(hidden, n_kv * hd, bias=False)
        self.v_proj = nn.Linear(hidden, n_kv * hd, bias=False)
        self.o_gate = nn.Linear(hidden, n_q * hd, bias=False)
        self.o_proj = nn.Linear(n_q * hd, hidden, bias=False)
        self.q_norm = RMSNorm(hd, config.rms_norm_eps)
        self.k_norm = RMSNorm(hd, config.rms_norm_eps)

    def forward(self, x):
        c = self.config
        b, t, _ = x.shape
        q = self.q_norm(self.q_proj(x).view(b, t, self.n_q, self.hd)).transpose(1, 2)
        k = self.k_norm(self.k_proj(x).view(b, t, self.n_kv, self.hd)).transpose(1, 2)
        v = self.v_proj(x).view(b, t, self.n_kv, self.hd).transpose(1, 2)
        pos = torch.arange(t)
        if self.kind == SPARSE:
            if t > c.sparse_dense_len:
                keep = sparse_mask(q, k, c)
            else:
                keep = (pos[None, :] <= pos[:, None])[None, None].expand(
                    b, self.n_kv, t, t
                )
            g = self.n_q // self.n_kv
            keep = keep.repeat_interleave(g, dim=1)
            k = k.repeat_interleave(g, dim=1)
            v = v.repeat_interleave(g, dim=1)
            s = (q @ k.transpose(-1, -2)) / math.sqrt(self.hd)
            s = s.masked_fill(~keep, float("-inf"))
            p = torch.softmax(s, dim=-1, dtype=torch.float32).to(q.dtype)
            out = (p @ v).transpose(1, 2).reshape(b, t, -1)
        else:
            q, k = _rope(q, c.rope_theta), _rope(k, c.rope_theta)
            a = torch.tensor(lightning_decays(self.n_q), dtype=torch.float32)
            diff = (pos[:, None] - pos[None, :]).to(torch.float32)
            mask = torch.where(
                diff >= 0, torch.exp(a[:, None, None] * diff.clamp(min=0)),
                torch.zeros(()),
            )
            s = (q @ k.transpose(-1, -2)) / math.sqrt(self.hd)
            out = ((s * mask) @ v).transpose(1, 2).reshape(b, t, -1)
            out = self.o_norm(out)
        return self.o_proj(out * torch.sigmoid(self.o_gate(x)))


class MiniCPMSALAMLP(nn.Module):
    def __init__(self, config: MiniCPMSALAConfig):
        super().__init__()
        h, f = config.hidden_size, config.intermediate_size
        self.gate_proj = nn.Linear(h, f, bias=False)
        self.up_proj = nn.Linear(h, f, bias=False)
        self.down_proj = nn.Linear(f, h, bias=False)

    def forward(self, x):
        return self.down_proj(
            torch.nn.functional.silu(self.gate_proj(x)) * self.up_proj(x)
        )


class MiniCPMSALADecoderLayer(nn.Module):
    def __init__(self, config: MiniCPMSALAConfig, layer_idx: int):
        super().__init__()
        hidden, eps = config.hidden_size, config.rms_norm_eps
        self.scale = config.scale_depth / math.sqrt(config.residual_layers)
        self.self_attn = MiniCPMSALAAttention(config, layer_idx)
        self.mlp = MiniCPMSALAMLP(config)
        self.input_layernorm = RMSNorm(hidden, eps)
        self.post_attention_layernorm = RMSNorm(hidden, eps)

    def forward(self, x):
        x = x + self.scale * self.self_attn(self.input_layernorm(x))
        return x + self.scale * self.mlp(self.post_attention_layernorm(x))


class MiniCPMSALAModel(nn.Module):
    def __init__(self, config: MiniCPMSALAConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size, config.hidden_size)
        self.layers = nn.ModuleList(
            MiniCPMSALADecoderLayer(config, i)
            for i in range(config.num_hidden_layers)
        )
        self.norm = RMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids) * self.config.scale_emb
        for layer in self.layers:
            x = layer(x)
        return self.norm(x)


class MiniCPMSALAForCausalLM(nn.Module):
    def __init__(self, config: MiniCPMSALAConfig):
        super().__init__()
        self.config = config
        self.model = MiniCPMSALAModel(config)
        self.lm_head = nn.Linear(config.hidden_size, config.vocab_size, bias=False)
        self.apply(self._init_weights)

    def _init_weights(self, module):
        if isinstance(module, nn.Linear):  # the embedding keeps its N(0, 1)
            module.weight.data.normal_(mean=0.0, std=self.config.initializer_range)

    def forward(self, input_ids):
        """Token ids ``(B, T)`` -> logits ``(B, T, V)``."""
        c = self.config
        h = self.model(input_ids) / (c.hidden_size / c.dim_model_base)
        return self.lm_head(h)
