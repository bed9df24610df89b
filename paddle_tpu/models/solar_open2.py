"""Solar Open 2: a decoder whose blocks are TWO sublayers under two norms.

``h <- h + Mixer_l(RMSNorm(h))``, then ``h <- h + Experts_l(RMSNorm(h))``.
The mixer of layer ``l`` is gated grouped-KV softmax attention if ``l`` is in
``gqa_layers`` (``num_key_value_heads`` K/V heads each serving a group of
query heads, NO positional encoding, the attention's output multiplied
elementwise by ``sigmoid(x W_z)`` before ``W_o``: "Gated Attention for
LLMs", arXiv:2505.06708), else Kimi Delta Attention (``nn.KimiDeltaAttention``:
a delta-rule linear-attention state with a decay per channel). Every layer's
second sublayer is dropless sigmoid-routed experts of the gated form
``down(silu(gate x) * up x)`` plus a shared expert (``nn.DroplessExperts``,
``form="swiglu"``). The residual stream stays in the model's dtype; the
embedding is a plain lookup and the head is untied. Every parameter is
created in ``cfg.dtype`` from the start (the recurrence's own, the router
and the norm gains in float32).

Expert parallelism by share, as ``models/nemotron_h.py``: ``cfg.held_experts``
lists the routed experts whose weights this instance holds (default: all);
the router still scores all ``n_routed_experts``. ``vocab_size`` is the rows
held of the embedding and the head.

Serving: :meth:`SolarOpen2ForCausalLM.cache_spec` lists one entry a SUBLAYER
that keeps or counts something: K/V rows or the KDA state for the mixer,
then the routing counts for the experts; ``forward(cache=)`` takes one view
an entry, in that order.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax

from .. import ops
from ..nn import functional as F
from ..nn.initializer import Normal
from ..nn.layer.container import LayerList
from ..nn.layer.experts import ROUTING_COUNTS, DroplessExperts
from ..nn.layer.kda import KimiDeltaAttention
from ..nn.layer.layers import Layer
from ..nn.layer.norm import RMSNorm

__all__ = ["SolarOpen2Config", "SolarOpen2Attention", "SolarOpen2Block",
           "SolarOpen2Model", "SolarOpen2ForCausalLM"]


@dataclass
class SolarOpen2Config:
    vocab_size: int = 196608
    hidden_size: int = 4096
    num_hidden_layers: int = 48
    gqa_layers: tuple = tuple(range(0, 48, 4))
    # gated grouped-KV attention
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    use_gqa_gate: bool = True
    # Kimi Delta Attention
    kda_num_heads: int = 64
    kda_head_dim: int = 128
    kda_conv_kernel: int = 4
    kda_gate_rank: int = 128
    kda_allow_neg_eigval: bool = True
    # experts
    n_routed_experts: int = 320
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 1280
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.0
    held_experts: tuple | None = None  # ids held here; None: all
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 1048576
    initializer_range: float = 0.02
    dtype: str = "bfloat16"


def _dense(layer, n_in, n_out, cfg):
    return layer.create_parameter(
        [n_in, n_out], dtype=cfg.dtype,
        default_initializer=Normal(std=cfg.initializer_range))


class SolarOpen2Attention(Layer):
    """Causal attention with grouped K/V heads, no positional encoding, no
    bias; with ``use_gqa_gate`` its output is gated channel by channel from
    the layer's input before the output projection."""

    def __init__(self, cfg: SolarOpen2Config):
        super().__init__()
        self.nq, self.nkv, self.d = (cfg.num_attention_heads,
                                     cfg.num_key_value_heads, cfg.head_dim)
        h = cfg.hidden_size
        self.q_proj = _dense(self, h, self.nq * self.d, cfg)
        self.k_proj = _dense(self, h, self.nkv * self.d, cfg)
        self.v_proj = _dense(self, h, self.nkv * self.d, cfg)
        self.gate_proj = _dense(self, h, self.nq * self.d, cfg) \
            if cfg.use_gqa_gate else None
        self.o_proj = _dense(self, self.nq * self.d, h, cfg)

    def forward(self, x, attn_mask=None, cache=None):
        b, s, _ = x.shape
        q = F.linear(x, self.q_proj).reshape([b, s, self.nq, self.d])
        k = F.linear(x, self.k_proj).reshape([b, s, self.nkv, self.d])
        v = F.linear(x, self.v_proj).reshape([b, s, self.nkv, self.d])
        if cache is not None:
            k, v, cache = cache.update(k, v)
        attn = F.scaled_dot_product_attention(
            q, k, v, attn_mask=attn_mask, is_causal=cache is None,
            training=False).reshape([b, s, self.nq * self.d])
        if self.gate_proj is not None:
            attn = attn * F.sigmoid(F.linear(x, self.gate_proj))
        return F.linear(attn, self.o_proj)


class SolarOpen2Block(Layer):
    def __init__(self, cfg: SolarOpen2Config, index):
        super().__init__()
        self.is_gqa = index in cfg.gqa_layers
        self.input_norm = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)
        if self.is_gqa:
            self.mixer = SolarOpen2Attention(cfg)
        else:
            self.mixer = KimiDeltaAttention(
                cfg.hidden_size, cfg.kda_num_heads, cfg.kda_head_dim,
                conv_kernel=cfg.kda_conv_kernel,
                gate_rank=cfg.kda_gate_rank, eps=cfg.rms_norm_eps,
                allow_neg_eigval=cfg.kda_allow_neg_eigval, dtype=cfg.dtype,
                init_std=cfg.initializer_range)
        self.post_norm = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)
        self.experts = DroplessExperts(
            cfg.hidden_size, cfg.moe_intermediate_size, cfg.n_routed_experts,
            cfg.num_experts_per_tok, held=cfg.held_experts,
            shared_width=cfg.n_shared_experts * cfg.moe_intermediate_size,
            scale=cfg.routed_scaling_factor, dtype=cfg.dtype,
            init_std=cfg.initializer_range, form="swiglu")

    def cache_spec(self, cfg):
        """What one served slot keeps for the two sublayers, in order."""
        mixer = {"kind": "kv", "heads": cfg.num_key_value_heads,
                 "head_dim": cfg.head_dim, "dtype": cfg.dtype} \
            if self.is_gqa \
            else {"kind": "state", "arrays": self.mixer.state_spec()}
        return [mixer, {"kind": "counts", "names": ROUTING_COUNTS}]

    def forward(self, h, attn_mask=None, cache=(None, None)):
        kept, counting = cache
        y = self.input_norm(h)
        if self.is_gqa:
            with jax.named_scope("attention"):
                h = h + self.mixer(y, attn_mask=attn_mask, cache=kept)
        else:
            with jax.named_scope("kda"):
                h = h + self.mixer(y, state=kept)
        with jax.named_scope("experts"):
            out, counts, _ = self.experts(
                self.post_norm(h),
                valid=None if counting is None else counting.valid)
        if counting is not None:
            counting.note(counts)
        return h + out


class SolarOpen2Model(Layer):
    def __init__(self, cfg: SolarOpen2Config):
        super().__init__()
        self.cfg = cfg
        self.embeddings = self.create_parameter(
            [cfg.vocab_size, cfg.hidden_size], dtype=cfg.dtype,
            default_initializer=Normal(std=cfg.initializer_range))
        self.layers = LayerList([SolarOpen2Block(cfg, i)
                                 for i in range(cfg.num_hidden_layers)])
        self.norm_f = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)

    def forward(self, input_ids, attn_mask=None, cache=None):
        h = F.embedding(input_ids, self.embeddings)
        views = cache if cache is not None else [None] * (2 * len(self.layers))
        for i, layer in enumerate(self.layers):
            h = layer(h, attn_mask=attn_mask, cache=views[2 * i:2 * i + 2])
        return self.norm_f(h)


class SolarOpen2ForCausalLM(Layer):
    def __init__(self, cfg: SolarOpen2Config):
        super().__init__()
        self.cfg = cfg
        self.backbone = SolarOpen2Model(cfg)
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
        # no positional encoding: ``position_ids`` is accepted for the
        # serving engine's call and not used
        del position_ids
        h = self.backbone(input_ids, attn_mask=attn_mask, cache=cache)
        with jax.named_scope("lm_head"):
            logits = ops.matmul(h, self.lm_head, transpose_y=True)
        return logits if cache is None else (logits, cache)
