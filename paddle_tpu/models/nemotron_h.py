"""Nemotron-H: a hybrid decoder whose blocks are ONE mixer each.

``hybrid_override_pattern`` picks every block's mixer, letter by letter:
``M`` a Mamba-2 mixer (``nn.Mamba2Mixer``), ``*`` grouped-KV attention with
no positional encoding, ``E`` dropless sigmoid-routed experts plus a shared
expert (``nn.DroplessExperts``). A block is ``h + mixer(RMSNorm(h))``; the
residual stream stays in the model's dtype; the embedding is a plain lookup
and the head is untied. Every parameter is created in ``cfg.dtype`` from the
start (the recurrence's own, the router and the norm gains in float32): a
model of this family does not fit its chip twice over in float32.

Expert parallelism by share: ``cfg.held_experts`` lists the routed experts
whose weights this instance holds (default: all); the router still scores
all ``n_routed_experts``. ``vocab_size`` is the rows held of the embedding
and the head.

Serving: :meth:`NemotronHForCausalLM.cache_spec` declares, block by block,
what a slot keeps — K/V rows for ``*``, the convolution window and the state
for ``M``, nothing but the routing counts for ``E`` — and
``serving.GenerationEngine`` allocates and threads exactly that.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax

from .. import ops
from ..nn import functional as F
from ..nn.initializer import Normal
from ..nn.layer.container import LayerList
from ..nn.layer.experts import ROUTING_COUNTS, DroplessExperts
from ..nn.layer.layers import Layer
from ..nn.layer.mamba import Mamba2Mixer
from ..nn.layer.norm import RMSNorm

__all__ = ["NemotronHConfig", "NemotronHAttention", "NemotronHBlock",
           "NemotronHModel", "NemotronHForCausalLM"]


@dataclass
class NemotronHConfig:
    vocab_size: int = 131072
    hidden_size: int = 2688
    hybrid_override_pattern: str = "MEM*E"
    # Mamba-2
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    # attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    # experts
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    routed_scaling_factor: float = 2.5
    held_experts: tuple | None = None  # ids held here; None: all
    layer_norm_epsilon: float = 1e-5
    max_position_embeddings: int = 262144
    initializer_range: float = 0.02
    dtype: str = "bfloat16"

    @property
    def num_layers(self):
        return len(self.hybrid_override_pattern)


class _Dense(Layer):
    """Bias-free projection created in the model's dtype."""

    def __init__(self, n_in, n_out, cfg):
        super().__init__()
        self.weight = self.create_parameter(
            [n_in, n_out], dtype=cfg.dtype,
            default_initializer=Normal(std=cfg.initializer_range))

    def forward(self, x):
        return F.linear(x, self.weight)


class NemotronHAttention(Layer):
    """Causal attention, ``num_key_value_heads`` K/V heads each serving a
    group of query heads, no positional encoding, no bias."""

    def __init__(self, cfg: NemotronHConfig):
        super().__init__()
        self.nq, self.nkv, self.d = (cfg.num_attention_heads,
                                     cfg.num_key_value_heads, cfg.head_dim)
        h = cfg.hidden_size
        self.q_proj = _Dense(h, self.nq * self.d, cfg)
        self.k_proj = _Dense(h, self.nkv * self.d, cfg)
        self.v_proj = _Dense(h, self.nkv * self.d, cfg)
        self.o_proj = _Dense(self.nq * self.d, h, cfg)

    def forward(self, x, attn_mask=None, cache=None):
        b, s, _ = x.shape
        q = self.q_proj(x).reshape([b, s, self.nq, self.d])
        k = self.k_proj(x).reshape([b, s, self.nkv, self.d])
        v = self.v_proj(x).reshape([b, s, self.nkv, self.d])
        if cache is not None:
            k, v, cache = cache.update(k, v)
        attn = F.scaled_dot_product_attention(
            q, k, v, attn_mask=attn_mask, is_causal=cache is None,
            training=False)
        return self.o_proj(attn.reshape([b, s, self.nq * self.d]))


class NemotronHBlock(Layer):
    def __init__(self, cfg: NemotronHConfig, kind):
        super().__init__()
        self.kind = kind
        self.norm = RMSNorm(cfg.hidden_size, epsilon=cfg.layer_norm_epsilon)
        if kind == "M":
            self.mixer = Mamba2Mixer(
                cfg.hidden_size, cfg.mamba_num_heads, cfg.mamba_head_dim,
                cfg.n_groups, cfg.ssm_state_size,
                conv_kernel=cfg.conv_kernel, chunk_size=cfg.chunk_size,
                eps=cfg.layer_norm_epsilon, dtype=cfg.dtype,
                init_std=cfg.initializer_range)
        elif kind == "*":
            self.mixer = NemotronHAttention(cfg)
        elif kind == "E":
            self.mixer = DroplessExperts(
                cfg.hidden_size, cfg.moe_intermediate_size,
                cfg.n_routed_experts, cfg.num_experts_per_tok,
                held=cfg.held_experts,
                shared_width=cfg.moe_shared_expert_intermediate_size,
                scale=cfg.routed_scaling_factor, dtype=cfg.dtype,
                init_std=cfg.initializer_range)
        else:
            raise ValueError(f"hybrid_override_pattern: unknown block kind "
                             f"{kind!r} (M, * or E)")

    def cache_spec(self, cfg):
        """What one served slot keeps for this block."""
        if self.kind == "*":
            return {"kind": "kv", "heads": cfg.num_key_value_heads,
                    "head_dim": cfg.head_dim, "dtype": cfg.dtype}
        if self.kind == "M":
            return {"kind": "state", "arrays": self.mixer.state_spec()}
        return {"kind": "counts", "names": ROUTING_COUNTS}

    def forward(self, h, attn_mask=None, cache=None):
        y = self.norm(h)
        if self.kind == "M":
            with jax.named_scope("mamba2"):
                out = self.mixer(y, state=cache)
        elif self.kind == "*":
            with jax.named_scope("attention"):
                out = self.mixer(y, attn_mask=attn_mask, cache=cache)
        else:
            with jax.named_scope("experts"):
                out, counts, _ = self.mixer(
                    y, valid=None if cache is None else cache.valid)
            if cache is not None:
                cache.note(counts)
        return h + out


class NemotronHModel(Layer):
    def __init__(self, cfg: NemotronHConfig):
        super().__init__()
        self.cfg = cfg
        self.embeddings = self.create_parameter(
            [cfg.vocab_size, cfg.hidden_size], dtype=cfg.dtype,
            default_initializer=Normal(std=cfg.initializer_range))
        self.layers = LayerList([NemotronHBlock(cfg, kind)
                                 for kind in cfg.hybrid_override_pattern])
        self.norm_f = RMSNorm(cfg.hidden_size, epsilon=cfg.layer_norm_epsilon)

    def forward(self, input_ids, attn_mask=None, cache=None):
        h = F.embedding(input_ids, self.embeddings)
        views = cache if cache is not None else [None] * len(self.layers)
        for layer, view in zip(self.layers, views):
            h = layer(h, attn_mask=attn_mask, cache=view)
        return self.norm_f(h)


class NemotronHForCausalLM(Layer):
    def __init__(self, cfg: NemotronHConfig):
        super().__init__()
        self.cfg = cfg
        self.backbone = NemotronHModel(cfg)
        self.lm_head = self.create_parameter(
            [cfg.vocab_size, cfg.hidden_size], dtype=cfg.dtype,
            default_initializer=Normal(std=cfg.initializer_range))

    def cache_spec(self):
        """Block by block, what ``serving.GenerationEngine`` allocates for a
        slot (see ``serving/kv_cache.py``)."""
        return [blk.cache_spec(self.cfg) for blk in self.backbone.layers]

    def forward(self, input_ids, position_ids=None, attn_mask=None,
                cache=None):
        # no positional encoding: ``position_ids`` is accepted for the
        # serving engine's call and not used
        del position_ids
        h = self.backbone(input_ids, attn_mask=attn_mask, cache=cache)
        with jax.named_scope("lm_head"):
            logits = ops.matmul(h, self.lm_head, transpose_y=True)
        return logits if cache is None else (logits, cache)
