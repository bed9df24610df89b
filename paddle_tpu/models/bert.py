"""BERT-family encoder models.

Reference scale target: the BERT configs the reference's fleet/AMP stack
trains (``python/paddle/fluid/tests/unittests/test_bert*`` and the
BERT-large tokens/sec/chip metric of its benchmarks). Encoder built from the
framework's TransformerEncoder; the MLM head reuses the fused
linear+cross-entropy op so the ``[tokens, vocab]`` logits never materialize
(ops/fused.py), same as the GPT flagship.

TPU notes: under the fleet hybrid mesh the encoder works with dp/sharding
out of the box (batch sharding + ZeRO placement); mp for BERT reuses the
Column/RowParallelLinear layers if wired into a custom encoder layer.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from ..framework.tensor import Tensor
from .. import ops
from ..nn import functional as F
from ..nn.initializer import Normal
from ..nn.layer.common import Dropout, Embedding, Linear
from ..nn.layer.layers import Layer, ParamAttr
from ..nn.layer.norm import LayerNorm
from ..nn.layer.transformer import TransformerEncoder, TransformerEncoderLayer

__all__ = ["BertConfig", "BertEmbeddings", "BertModel", "BertPooler",
           "BertForPretraining", "BertForSequenceClassification"]


@dataclass
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    hidden_dropout: float = 0.1
    attention_dropout: float = 0.1
    initializer_range: float = 0.02
    layer_norm_eps: float = 1e-12


def bert_base():
    return BertConfig()


def bert_large():
    return BertConfig(hidden_size=1024, num_layers=24, num_heads=16,
                      intermediate_size=4096)


class BertEmbeddings(Layer):
    """word + position + token-type embeddings -> LN -> dropout."""

    def __init__(self, cfg: BertConfig):
        super().__init__()
        init = ParamAttr(initializer=Normal(std=cfg.initializer_range))
        self.word_embeddings = Embedding(cfg.vocab_size, cfg.hidden_size,
                                         weight_attr=init)
        self.position_embeddings = Embedding(cfg.max_position_embeddings,
                                             cfg.hidden_size, weight_attr=init)
        self.token_type_embeddings = Embedding(cfg.type_vocab_size,
                                               cfg.hidden_size,
                                               weight_attr=init)
        self.layer_norm = LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)
        self.dropout = Dropout(cfg.hidden_dropout, mode="upscale_in_train")

    def forward(self, input_ids, token_type_ids=None, position_ids=None):
        b, s = input_ids.shape
        if position_ids is None:
            # (1, s): the embedding broadcasts over batch — materializing
            # the batch dim would force a constant where dynamic-batch
            # export (symbolic b) must stay polymorphic
            import jax.numpy as jnp

            position_ids = Tensor(jnp.arange(s, dtype=jnp.int32)[None, :])
        if token_type_ids is None:
            token_type_ids = ops.zeros_like(input_ids)
        h = (self.word_embeddings(input_ids)
             + self.position_embeddings(position_ids)
             + self.token_type_embeddings(token_type_ids))
        return self.dropout(self.layer_norm(h))


class BertPooler(Layer):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.dense = Linear(cfg.hidden_size, cfg.hidden_size)

    def forward(self, h):
        return self.dense(h[:, 0]).tanh()


class BertModel(Layer):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.cfg = cfg
        self.embeddings = BertEmbeddings(cfg)
        enc_layer = TransformerEncoderLayer(
            cfg.hidden_size, cfg.num_heads, cfg.intermediate_size,
            dropout=cfg.hidden_dropout, activation="gelu",
            attn_dropout=cfg.attention_dropout,
            act_dropout=0.0, normalize_before=False,
            layer_norm_eps=cfg.layer_norm_eps)
        self.encoder = TransformerEncoder(enc_layer, cfg.num_layers)
        self.pooler = BertPooler(cfg)

    def forward(self, input_ids, token_type_ids=None, position_ids=None,
                attention_mask=None):
        """attention_mask: [b, s] 1/0 padding mask (paddle convention) or a
        broadcastable additive mask."""
        h = self.embeddings(input_ids, token_type_ids, position_ids)
        mask = None
        if attention_mask is not None:
            if len(attention_mask.shape) == 2:
                # [b, s] keep-mask -> additive [b, 1, 1, s]
                neg = (1.0 - attention_mask.astype("float32")) * -1e4
                mask = neg.unsqueeze(1).unsqueeze(2)
            else:
                mask = attention_mask
        out = self.encoder(h, src_mask=mask)
        return out, self.pooler(out)


class BertForPretraining(Layer):
    """MLM + NSP heads (reference BertForPretraining); the MLM loss uses the
    fused linear+CE path with the tied word-embedding matrix."""

    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.cfg = cfg
        self.bert = BertModel(cfg)
        self.transform = Linear(cfg.hidden_size, cfg.hidden_size)
        self.transform_ln = LayerNorm(cfg.hidden_size,
                                      epsilon=cfg.layer_norm_eps)
        self.nsp_head = Linear(cfg.hidden_size, 2)

    def _mlm_hidden(self, input_ids, token_type_ids, attention_mask):
        """Shared MLM head pipeline: encoder -> transform -> gelu -> LN."""
        seq, pooled = self.bert(input_ids, token_type_ids,
                                attention_mask=attention_mask)
        h = self.transform_ln(F.gelu(self.transform(seq), approximate=True))
        return h, pooled

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        h, pooled = self._mlm_hidden(input_ids, token_type_ids, attention_mask)
        w = self.bert.embeddings.word_embeddings.weight
        logits = ops.matmul(h, w, transpose_y=True)
        return logits, self.nsp_head(pooled)

    def loss(self, input_ids, mlm_labels, token_type_ids=None,
             attention_mask=None, nsp_labels=None, ignore_index=-100):
        """Fused MLM loss (+ optional NSP)."""
        h, pooled = self._mlm_hidden(input_ids, token_type_ids, attention_mask)
        w = self.bert.embeddings.word_embeddings.weight
        loss = F.fused_linear_cross_entropy(h, w, mlm_labels,
                                            ignore_index=ignore_index)
        if nsp_labels is not None:
            loss = loss + F.cross_entropy(
                self.nsp_head(pooled), nsp_labels.reshape([-1, 1])).mean()
        return loss


class BertForSequenceClassification(Layer):
    def __init__(self, cfg: BertConfig, num_classes=2, dropout=None):
        super().__init__()
        self.bert = BertModel(cfg)
        self.dropout = Dropout(cfg.hidden_dropout if dropout is None else dropout,
                               mode="upscale_in_train")
        self.classifier = Linear(cfg.hidden_size, num_classes)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        _, pooled = self.bert(input_ids, token_type_ids,
                              attention_mask=attention_mask)
        return self.classifier(self.dropout(pooled))

    def scorer(self, max_batch=8, seq_buckets=None, max_seq=None):
        """Serving path: a bucketed compile-once-per-bucket batch scorer
        (:class:`paddle_tpu.serving.EncoderScorer`) — requests are padded
        to ``[max_batch, bucket]`` so one executable per sequence bucket
        serves every request mix; padding rows are masked and dropped."""
        from ..serving import EncoderScorer

        return EncoderScorer(self, max_batch=max_batch,
                             seq_buckets=seq_buckets, max_seq=max_seq)
