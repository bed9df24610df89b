"""Laguna: a decoder whose attention layers come in two kinds.

``h <- h + Attn_l(RMSNorm(h))``, then ``h <- h + MLP_l(RMSNorm(h))``. Layer
``l`` attends to every earlier position (``layer_types[l]`` is
``full_attention``) or to the last ``sliding_window`` alone
(``sliding_attention``), with its own number of query heads
(``num_attention_heads_per_layer``) over ``num_key_value_heads`` K/V heads,
and its own rotary form (``rope_parameters[layer type]``: plain over the
whole head, or YaRN over part of it, ``nn/functional/rope.py``). q and k are
rotated at each token's own position BEFORE k is cached. With ``gating`` the
attention's output is multiplied head by head by ``sigmoid(x w_g)``, one
gate a head from the layer's input ("Gated Attention for LLMs",
arXiv:2505.06708, head-wise), before ``W_o``. ``MLP_l`` is a dense gated MLP
(``mlp_layer_types[l]`` is ``dense``) or dropless sigmoid-routed experts of
the gated form plus a shared expert (``sparse``; ``nn.DroplessExperts``,
``form="swiglu"``). The embedding is a plain lookup and the head is untied.
Every parameter is created in ``cfg.dtype`` from the start (the router and
the norm gains in float32; the rotary frequencies are float32 constants).

Expert parallelism by share, as ``models/nemotron_h.py``: ``cfg.held_experts``
lists the routed experts whose weights this instance holds (default: all);
the router still scores all ``num_experts``. ``vocab_size`` is the rows held
of the embedding and the head.

Serving: :meth:`LagunaForCausalLM.cache_spec` lists two entries a layer, its
K/V rows and what its MLP counts. A window layer's entry carries
``"window"``: the engine keeps it as a ring of that many rows
(``serving/kv_cache.py``) and hands the layer the ring's own mask on its
view. ``forward(cache=)`` takes one view an entry, in that order.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from .. import ops
from ..nn import functional as F
from ..nn.functional.rope import apply_rotary, rope_frequencies
from ..nn.initializer import Normal
from ..nn.layer.container import LayerList
from ..nn.layer.experts import ROUTING_COUNTS, DroplessExperts, _swiglu_mlp
from ..nn.layer.layers import Layer
from ..nn.layer.norm import RMSNorm

__all__ = ["LagunaConfig", "LagunaAttention", "LagunaBlock", "LagunaModel",
           "LagunaForCausalLM"]

FULL, WINDOW = "full_attention", "sliding_attention"


def _published_rope():
    return {
        FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 64,
               "original_max_position_embeddings": 4096, "beta_slow": 1,
               "beta_fast": 64, "attention_factor": 1.4158883083359672,
               "partial_rotary_factor": 0.5},
        WINDOW: {"rope_type": "default", "rope_theta": 10000,
                 "partial_rotary_factor": 1},
    }


@dataclass
class LagunaConfig:
    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192
    num_hidden_layers: int = 40
    layer_types: tuple = (FULL, WINDOW, WINDOW, WINDOW) * 10
    mlp_layer_types: tuple = ("dense",) + ("sparse",) * 39
    num_attention_heads_per_layer: tuple = (48, 64, 64, 64) * 10
    num_key_value_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 512
    rope_parameters: dict = field(default_factory=_published_rope)
    gating: bool = True
    # experts
    num_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    moe_routed_scaling_factor: float = 2.5
    held_experts: tuple | None = None  # ids held here; None: all
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 262144
    initializer_range: float = 0.02
    dtype: str = "bfloat16"


def _dense(layer, n_in, n_out, cfg):
    return layer.create_parameter(
        [n_in, n_out], dtype=cfg.dtype,
        default_initializer=Normal(std=cfg.initializer_range))


class LagunaAttention(Layer):
    """Causal attention with grouped K/V heads, rotary positions and no
    bias; ``window`` (None: every earlier position) bounds how far back a
    query sees; with ``gated`` one sigmoid gate a head."""

    def __init__(self, cfg: LagunaConfig, heads, window, rope):
        super().__init__()
        self.nq, self.nkv, self.d = (int(heads), cfg.num_key_value_heads,
                                     cfg.head_dim)
        self.window = window
        h = cfg.hidden_size
        self.q_proj = _dense(self, h, self.nq * self.d, cfg)
        self.k_proj = _dense(self, h, self.nkv * self.d, cfg)
        self.v_proj = _dense(self, h, self.nkv * self.d, cfg)
        self.gate_proj = _dense(self, h, self.nq, cfg) if cfg.gating else None
        self.o_proj = _dense(self, self.nq * self.d, h, cfg)
        kind = rope.get("rope_type", "default")
        if kind not in ("default", "yarn"):
            raise ValueError(f"rope_type {kind!r}: 'default' or 'yarn'")
        # fixed here, once: constants of the compiled steps
        self.inv_freq, self.rope_scale = rope_frequencies(
            int(self.d * rope.get("partial_rotary_factor", 1)),
            rope["rope_theta"], yarn=rope if kind == "yarn" else None)

    def _mask(self, attn_mask, cache, position_ids):
        """A view that carries a mask (a ring's) decides; else the step's
        mask, narrowed to this layer's window."""
        own = getattr(cache, "mask", None)
        if own is not None or self.window is None:
            return own if own is not None else attn_mask
        if attn_mask is None:
            return F.LengthMask(position_ids, window=self.window)
        return F.LengthMask(attn_mask.q_pos, attn_mask.kv_len, self.window)

    def forward(self, x, position_ids, attn_mask=None, cache=None):
        b, s, _ = x.shape
        q = F.linear(x, self.q_proj).reshape([b, s, self.nq, self.d])
        k = F.linear(x, self.k_proj).reshape([b, s, self.nkv, self.d])
        v = F.linear(x, self.v_proj).reshape([b, s, self.nkv, self.d])
        q = apply_rotary(q, position_ids, self.inv_freq, self.rope_scale)
        k = apply_rotary(k, position_ids, self.inv_freq, self.rope_scale)
        mask = self._mask(attn_mask, cache, position_ids)
        if cache is not None:
            k, v, cache = cache.update(k, v)
        attn = F.scaled_dot_product_attention(
            q, k, v, attn_mask=mask, is_causal=mask is None, training=False)
        if self.gate_proj is not None:
            attn = attn * F.sigmoid(F.linear(x, self.gate_proj)).reshape(
                [b, s, self.nq, 1])
        return F.linear(attn.reshape([b, s, self.nq * self.d]), self.o_proj)


class LagunaBlock(Layer):
    def __init__(self, cfg: LagunaConfig, index):
        super().__init__()
        kind = cfg.layer_types[index]
        if kind not in (FULL, WINDOW):
            raise ValueError(f"layer_types[{index}] = {kind!r}")
        self.window = cfg.sliding_window if kind == WINDOW else None
        self.sparse = cfg.mlp_layer_types[index] == "sparse"
        self.input_norm = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)
        self.mixer = LagunaAttention(
            cfg, cfg.num_attention_heads_per_layer[index], self.window,
            cfg.rope_parameters[kind])
        self.post_norm = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)
        if self.sparse:
            self.experts = DroplessExperts(
                cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts,
                cfg.num_experts_per_tok, held=cfg.held_experts,
                shared_width=cfg.shared_expert_intermediate_size,
                scale=cfg.moe_routed_scaling_factor, dtype=cfg.dtype,
                init_std=cfg.initializer_range, form="swiglu")
        else:  # gate's columns, then up's, as the experts keep theirs
            self.mlp_gate_up = _dense(self, cfg.hidden_size,
                                      2 * cfg.intermediate_size, cfg)
            self.mlp_down = _dense(self, cfg.intermediate_size,
                                   cfg.hidden_size, cfg)

    def cache_spec(self, cfg):
        """What one served slot keeps for the two sublayers, in order."""
        kv = {"kind": "kv", "heads": cfg.num_key_value_heads,
              "head_dim": cfg.head_dim, "dtype": cfg.dtype}
        if self.window is not None:
            kv["window"] = self.window
        return [kv, {"kind": "counts", "names": ROUTING_COUNTS}
                if self.sparse else None]

    def forward(self, h, position_ids, attn_mask=None, cache=(None, None)):
        kept, counting = cache
        with jax.named_scope("attention_full" if self.window is None
                             else "attention_window"):
            h = h + self.mixer(self.input_norm(h), position_ids,
                               attn_mask=attn_mask, cache=kept)
        y = self.post_norm(h)
        if not self.sparse:
            with jax.named_scope("mlp"):
                return h + _swiglu_mlp(y, self.mlp_gate_up, self.mlp_down)
        with jax.named_scope("experts"):
            out, counts, _ = self.experts(
                y, valid=None if counting is None else counting.valid)
        if counting is not None:
            counting.note(counts)
        return h + out


class LagunaModel(Layer):
    def __init__(self, cfg: LagunaConfig):
        super().__init__()
        self.cfg = cfg
        self.embeddings = self.create_parameter(
            [cfg.vocab_size, cfg.hidden_size], dtype=cfg.dtype,
            default_initializer=Normal(std=cfg.initializer_range))
        self.layers = LayerList([LagunaBlock(cfg, i)
                                 for i in range(cfg.num_hidden_layers)])
        self.norm_f = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)

    def forward(self, input_ids, position_ids=None, attn_mask=None,
                cache=None):
        h = F.embedding(input_ids, self.embeddings)
        if position_ids is None:
            b, s = input_ids.shape
            position_ids = jnp.broadcast_to(
                jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))
        views = cache if cache is not None else [None] * (2 * len(self.layers))
        for i, layer in enumerate(self.layers):
            h = layer(h, position_ids, attn_mask=attn_mask,
                      cache=views[2 * i:2 * i + 2])
        return self.norm_f(h)


class LagunaForCausalLM(Layer):
    def __init__(self, cfg: LagunaConfig):
        super().__init__()
        self.cfg = cfg
        self.backbone = LagunaModel(cfg)
        self.lm_head = self.create_parameter(
            [cfg.vocab_size, cfg.hidden_size], dtype=cfg.dtype,
            default_initializer=Normal(std=cfg.initializer_range))

    def cache_spec(self):
        """Sublayer by sublayer, what ``serving.GenerationEngine`` allocates
        for a slot (see ``serving/kv_cache.py``)."""
        return [entry for blk in self.backbone.layers
                for entry in blk.cache_spec(self.cfg)]

    def forward(self, input_ids, position_ids=None, attn_mask=None,
                cache=None):
        h = self.backbone(input_ids, position_ids=position_ids,
                          attn_mask=attn_mask, cache=cache)
        with jax.named_scope("lm_head"):
            logits = ops.matmul(h, self.lm_head, transpose_y=True)
        return logits if cache is None else (logits, cache)
