"""GPT-family decoder-only transformer — the flagship model.

Reference scale target: the fleet hybrid-parallel trainings the reference is
built for (``fleet/meta_parallel/`` + rank scripts
``unittests/hybrid_parallel_pp_transformer.py``): pre-LN GPT blocks, tied
input/output embeddings, trained under any mix of dp/mp/pp/sharding/sep.

TPU-native design:
  * TP: when the fleet hybrid mesh has mp_degree>1 the QKV/MLP projections
    become Column/RowParallelLinear and the embedding VocabParallelEmbedding
    (weight-sharding annotations; XLA inserts the collectives).
  * PP: ``build_gpt_pipeline_descs`` expresses the same model as
    PipelineLayer descs with tied embeddings via SharedLayerDesc.
  * PP: ``build_pipelined_gpt`` (meta_parallel.pipeline_schedule) runs the
    decoder stack as a jitted SPMD 1F1B pipeline over the ``pp`` axis.
  * Long context: causal sdpa uses the Pallas flash-attention kernel when
    available; from ``BLOCKWISE_MIN_KV`` keys up the fallback is
    the blockwise online-softmax KV scan (``functional.attention``,
    ISSUE 15) — O(s·d) live bytes, never the O(s²) einsum score matrix —
    and short sequences keep the fused-einsum XLA path. The serving tier
    reaches the same route by passing ``LengthMask``es; training under an
    HBM budget adds the selective-remat autopilot via
    ``Model.prepare(..., remat=...)`` (``analysis/remat_plan.py``).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax
import numpy as np

from ..framework.tensor import Tensor
from .. import ops
from ..utils import warn_once
from ..nn import functional as F
from ..nn.initializer import Normal
from ..nn.layer.common import Dropout, Embedding, Linear
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer, ParamAttr
from ..nn.layer.norm import LayerNorm

__all__ = [
    "GPTConfig",
    "GPTEmbeddings",
    "GPTDecoderLayer",
    "GPTModel",
    "GPTForCausalLM",
    "build_gpt_pipeline_descs",
]


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 0  # 0 → 4*hidden
    max_position_embeddings: int = 1024
    hidden_dropout: float = 0.1
    attention_dropout: float = 0.1
    initializer_range: float = 0.02
    layer_norm_eps: float = 1e-5
    use_tp: bool = False       # tensor-parallel projections (needs fleet mp>1)
    use_sep: bool = False      # ring-attention sequence parallelism (sep>1)
    tie_embeddings: bool = True

    @property
    def ffn_size(self):
        return self.intermediate_size or 4 * self.hidden_size


def _mp_degree():
    from ..distributed.fleet.base.fleet_base import get_hybrid_communicate_group

    hcg = get_hybrid_communicate_group()
    return hcg.get_model_parallel_world_size() if hcg is not None else 1


def _sep_degree():
    from ..distributed.fleet.base.fleet_base import get_hybrid_communicate_group

    hcg = get_hybrid_communicate_group()
    return hcg.get_sep_parallel_world_size() if hcg is not None else 1


class GPTEmbeddings(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        init = ParamAttr(initializer=Normal(std=cfg.initializer_range))
        if cfg.use_tp and _mp_degree() > 1:
            from ..distributed.meta_parallel import VocabParallelEmbedding

            self.word_embeddings = VocabParallelEmbedding(
                cfg.vocab_size, cfg.hidden_size, weight_attr=init
            )
        else:
            self.word_embeddings = Embedding(
                cfg.vocab_size, cfg.hidden_size, weight_attr=init
            )
        self.position_embeddings = Embedding(
            cfg.max_position_embeddings, cfg.hidden_size, weight_attr=init
        )
        self.dropout = Dropout(cfg.hidden_dropout, mode="upscale_in_train")

    def forward(self, input_ids, position_ids=None):
        if position_ids is None:
            seq = input_ids.shape[1]
            position_ids = Tensor(
                np.arange(seq, dtype=np.int64)[None, :].repeat(input_ids.shape[0], 0)
            )
        h = self.word_embeddings(input_ids) + self.position_embeddings(position_ids)
        return self.dropout(h)


class GPTDecoderLayer(Layer):
    """Pre-LN causal block: LN → attn → +res → LN → MLP(gelu) → +res."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        h, nh = cfg.hidden_size, cfg.num_heads
        self.head_dim = h // nh
        init = ParamAttr(initializer=Normal(std=cfg.initializer_range))
        out_init = ParamAttr(
            initializer=Normal(std=cfg.initializer_range / math.sqrt(2 * cfg.num_layers))
        )
        tp = cfg.use_tp and _mp_degree() > 1
        if tp:
            from ..distributed.meta_parallel import (
                ColumnParallelLinear,
                RowParallelLinear,
            )

            self.qkv_proj = ColumnParallelLinear(h, 3 * h, weight_attr=init, gather_output=False)
            self.out_proj = RowParallelLinear(h, h, weight_attr=out_init, input_is_parallel=True)
            self.up_proj = ColumnParallelLinear(h, cfg.ffn_size, weight_attr=init, gather_output=False)
            self.down_proj = RowParallelLinear(cfg.ffn_size, h, weight_attr=out_init, input_is_parallel=True)
        else:
            self.qkv_proj = Linear(h, 3 * h, weight_attr=init)
            self.out_proj = Linear(h, h, weight_attr=out_init)
            self.up_proj = Linear(h, cfg.ffn_size, weight_attr=init)
            self.down_proj = Linear(cfg.ffn_size, h, weight_attr=out_init)
        self.ln_1 = LayerNorm(h, epsilon=cfg.layer_norm_eps)
        self.ln_2 = LayerNorm(h, epsilon=cfg.layer_norm_eps)
        self.attn_dropout = cfg.attention_dropout
        self.resid_dropout = Dropout(cfg.hidden_dropout, mode="upscale_in_train")
        self.num_heads = nh
        # ring-attention sequence parallelism over the sep mesh axis
        # (distributed/meta_parallel/sequence_parallel.py — green-field,
        # SURVEY §5; the reference has no SP/CP path)
        self._use_sep = cfg.use_sep and _sep_degree() > 1

    def forward(self, x, attn_mask=None, cache=None):
        # named scopes: the profiler's op metadata groups by layer part
        with jax.named_scope("attention"):
            x, cache = self._attention(x, attn_mask, cache)
        with jax.named_scope("mlp"):
            residual = x
            y = self.ln_2(x)
            y = self.down_proj(F.gelu(self.up_proj(y), approximate=True))
            out = residual + self.resid_dropout(y)
        return out if cache is None else (out, cache)

    def _attention(self, x, attn_mask, cache):
        b, s, h = x.shape
        residual = x
        y = self.ln_1(x)
        qkv = self.qkv_proj(y)
        # local head count follows the (possibly mp-sharded) projection width
        local_width = qkv.shape[-1] // 3
        nh_local = max(1, self.num_heads * local_width // h)
        qkv = qkv.reshape([b, s, 3, nh_local, self.head_dim])
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        if cache is not None:
            if isinstance(cache, (tuple, list)):
                # DEPRECATED grow-by-concat path: every step changes the
                # cache operand shape (one XLA executable per position — the
                # analysis `kv-cache-concat` rule flags exactly this) and
                # the concat re-materializes the full K/V in HBM per step.
                # Kept as a shim for old callers; detach() here only drops
                # autograd linkage — the arrays are shared, not copied.
                warn_once(
                    "gpt-kv-cache-concat",
                    "tuple KV cache on GPTDecoderLayer is deprecated: it "
                    "grows by concat and recompiles the decode step at "
                    "every position. Use paddle_tpu.serving.KVCache / "
                    "GenerationEngine for O(1) static-shape decode.")
                k = ops.concat([cache[0], k], axis=1)
                v = ops.concat([cache[1], v], axis=1)
                cache = (k.detach(), v.detach())
            else:
                # serving.KVCache view (DecodeView/PrefillView): writes the
                # new rows in place (dynamic_update_slice at a traced
                # position index) and returns shape-stable K/V — the O(1)
                # decode path; causality/validity live in attn_mask
                k, v, cache = cache.update(k, v)
        if self._use_sep and cache is None and attn_mask is None:
            from ..distributed.meta_parallel import ring_attention

            attn = ring_attention(
                q, k, v, is_causal=True,
                dropout_p=self.attn_dropout if self.training else 0.0)
        else:
            attn = F.scaled_dot_product_attention(
                q, k, v, attn_mask=attn_mask,
                dropout_p=self.attn_dropout if self.training else 0.0,
                is_causal=cache is None,
            )
        attn = attn.reshape([b, s, local_width])
        return residual + self.resid_dropout(self.out_proj(attn)), cache


class GPTModel(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.embeddings = GPTEmbeddings(cfg)
        self.layers = LayerList([GPTDecoderLayer(cfg) for _ in range(cfg.num_layers)])
        self.ln_f = LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)

    def forward(self, input_ids, position_ids=None, attn_mask=None,
                cache=None):
        h = self.embeddings(input_ids, position_ids)
        if cache is not None:
            # serving decode/prefill: one cache view per layer, collected
            # back for the engine (single-chip path; sep/mp stay training)
            new_cache = []
            for layer, c in zip(self.layers, cache):
                h, c = layer(h, attn_mask=attn_mask, cache=c)
                new_cache.append(c)
            return self.ln_f(h), new_cache
        # gate on the layers' frozen decision (made at construction against
        # the then-active hybrid mesh) so annotation and attention path agree
        if len(self.layers) and self.layers[0]._use_sep:
            from ..distributed.meta_parallel import split_sequence

            # keep activations sequence-sharded over sep between blocks
            h = split_sequence(h)
        for layer in self.layers:
            h = layer(h, attn_mask=attn_mask)
        return self.ln_f(h)


class GPTForCausalLM(Layer):
    """LM head tied to the input embedding (reference tied-weight pattern,
    SharedLayerDesc in PP)."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.gpt = GPTModel(cfg)

    def cache_spec(self):
        """What a served slot keeps, layer by layer
        (``serving.GenerationEngine`` allocates it): K and V rows of
        ``num_heads`` heads in every layer, in the embedding's dtype."""
        cfg = self.cfg
        dtype = self.gpt.embeddings.word_embeddings.weight.dtype
        return [{"kind": "kv", "heads": cfg.num_heads,
                 "head_dim": cfg.hidden_size // cfg.num_heads,
                 "dtype": dtype}] * cfg.num_layers

    def forward(self, input_ids, position_ids=None, attn_mask=None,
                cache=None):
        w = self.gpt.embeddings.word_embeddings.weight  # [vocab, hidden]
        if cache is not None:
            h, new_cache = self.gpt(input_ids, position_ids, attn_mask,
                                    cache=cache)
            with jax.named_scope("lm_head"):
                return ops.matmul(h, w, transpose_y=True), new_cache
        h = self.gpt(input_ids, position_ids, attn_mask)
        with jax.named_scope("lm_head"):
            return ops.matmul(h, w, transpose_y=True)

    def generate(self, prompt_ids, max_new_tokens=32, eos_id=None,
                 max_len=None, prefill_buckets=None):
        """Greedy generation through the O(1) static-shape KV cache
        (:class:`paddle_tpu.serving.GenerationEngine`, batch 1). The
        engine — and its compiled prefill/decode executables — is cached
        on the model, so repeated calls never recompile. For concurrent
        request serving use ``serving.Scheduler`` directly."""
        from ..serving import GenerationEngine

        key = (max_len, tuple(prefill_buckets) if prefill_buckets else None)
        eng = getattr(self, "_serve_engine", None)
        if eng is None or getattr(self, "_serve_engine_key", None) != key:
            eng = GenerationEngine(self, max_batch=1, max_len=max_len,
                                   prefill_buckets=prefill_buckets)
            self._serve_engine = eng
            self._serve_engine_key = key
        return eng.generate(prompt_ids, max_new_tokens=max_new_tokens,
                            eos_id=eos_id)

    def loss(self, input_ids, labels):
        """Fused LM loss: head matmul + softmax-CE without materializing the
        ``[tokens, vocab]`` logits (``ops.fused.fused_linear_cross_entropy``)."""
        h = self.gpt(input_ids)
        w = self.gpt.embeddings.word_embeddings.weight
        return F.fused_linear_cross_entropy(h, w, labels)


# ---------------------------------------------------------------------------
# pipeline form
# ---------------------------------------------------------------------------

def build_gpt_pipeline_descs(cfg: GPTConfig):
    """Express GPTForCausalLM as PipelineLayer descs (reference
    ``hybrid_parallel_pp_transformer.py`` / pp_layers LayerDesc list), with
    the embedding shared between the first stage and the LM head."""
    from ..distributed.meta_parallel import LayerDesc, SharedLayerDesc

    def emb_forward(layer, x):
        return layer(x)

    def head_forward(layer, h):
        w = layer.word_embeddings.weight
        return ops.matmul(h, w, transpose_y=True)

    descs = [
        SharedLayerDesc("embed", GPTEmbeddings, forward_func=emb_forward, cfg=cfg),
    ]
    descs += [LayerDesc(GPTDecoderLayer, cfg) for _ in range(cfg.num_layers)]
    descs += [
        LayerDesc(LayerNorm, cfg.hidden_size, epsilon=cfg.layer_norm_eps),
        SharedLayerDesc("embed", GPTEmbeddings, forward_func=head_forward, cfg=cfg),
    ]
    return descs
