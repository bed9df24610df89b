"""BERT model family + new vision models (DenseNet/AlexNet/SqueezeNet).
References: python/paddle/vision/models/."""
import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.models import (BertConfig, BertForPretraining,
                               BertForSequenceClassification, BertModel,
                               bert_base, bert_large)
from paddle_tpu.utils import unique_name

from tests.op_test import forward_shapes


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """Dodge the conftest KNOWN HAZARD: a same-host persistent-cache
    round-trip of this module's executables SIGABRTs mid-suite
    (cpu_aot_loader), and whether the broken deserialization path is hit
    depends on which in-memory executables the preceding modules left
    behind. Compile fresh for this module instead of loading from the
    cache. Flipping the flag alone is not enough — jax memoizes the
    use-the-cache decision at the first compile of the process
    (compilation_cache._cache_checked), so reset it on the way in AND on
    the way out to restore warm-cache behavior for later modules."""
    from jax._src import compilation_cache

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    compilation_cache.reset_cache()


def _tiny_cfg():
    return BertConfig(vocab_size=128, hidden_size=32, num_layers=2,
                      num_heads=2, intermediate_size=64,
                      max_position_embeddings=64, type_vocab_size=2,
                      hidden_dropout=0.0, attention_dropout=0.0)


def test_bert_configs():
    assert bert_base().num_layers == 12
    lg = bert_large()
    assert lg.hidden_size == 1024 and lg.num_layers == 24 and lg.num_heads == 16


def test_bert_forward_shapes_and_padding_mask():
    paddle.seed(0)
    m = BertModel(_tiny_cfg())
    m.eval()
    ids = Tensor(np.random.RandomState(0).randint(0, 128, (2, 16)).astype(np.int64))
    seq, pooled = m(ids)
    assert list(seq.shape) == [2, 16, 32] and list(pooled.shape) == [2, 32]

    # padding mask: padded positions must not affect unpadded outputs
    mask = np.ones((2, 16), np.float32)
    mask[:, 12:] = 0.0
    seq_m, _ = m(ids, attention_mask=Tensor(mask))
    ids2 = np.asarray(ids._value).copy()
    ids2[:, 12:] = 7  # change padded content
    seq_m2, _ = m(Tensor(ids2), attention_mask=Tensor(mask))
    np.testing.assert_allclose(np.asarray(seq_m._value)[:, :12],
                               np.asarray(seq_m2._value)[:, :12], atol=1e-5)


def test_bert_pretraining_trains_with_fused_mlm():
    paddle.seed(1)
    model = BertForPretraining(_tiny_cfg())
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    rng = np.random.RandomState(1)
    ids = Tensor(rng.randint(0, 128, (4, 16)).astype(np.int64))
    labels = rng.randint(0, 128, (4, 16)).astype(np.int64)
    labels[:, ::3] = -100  # unmasked positions ignored
    nsp = Tensor(rng.randint(0, 2, (4,)).astype(np.int64))

    from paddle_tpu.jit.functionalize import CompiledStep

    def step(ids, mlm, nsp):
        loss = model.loss(ids, mlm, nsp_labels=nsp)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    cs = CompiledStep(step, stateful=[model, opt])
    l0 = float(np.asarray(cs(ids, Tensor(labels), nsp)._value))
    for _ in range(6):
        l1 = float(np.asarray(cs(ids, Tensor(labels), nsp)._value))
    assert np.isfinite(l1) and l1 < l0

    # fused loss == unfused full-logits loss
    model.eval()
    logits, _ = model(ids)
    import paddle_tpu.nn.functional as F

    fused = float(np.asarray(model.loss(ids, Tensor(labels))._value))
    ref2 = float(np.asarray(F.cross_entropy(
        logits.reshape([-1, 128]), Tensor(labels.reshape(-1, 1)),
        ignore_index=-100)._value))
    np.testing.assert_allclose(fused, ref2, rtol=1e-5)


def test_bert_classifier():
    paddle.seed(2)
    m = BertForSequenceClassification(_tiny_cfg(), num_classes=3)
    m.eval()
    ids = Tensor(np.random.RandomState(2).randint(0, 128, (2, 8)).astype(np.int64))
    out = m(ids)
    assert list(out.shape) == [2, 3]


@pytest.mark.parametrize("factory,expect_params", [
    ("densenet121", None), ("alexnet", None), ("squeezenet1_1", None),
])
def test_vision_models_forward(factory, expect_params):
    from paddle_tpu.vision import models as M

    paddle.seed(3)
    net = getattr(M, factory)(num_classes=10)
    net.eval()
    assert forward_shapes(net, 1, 3, 64, 64) == [[1, 10]]
    assert len(net.parameters()) > 5
    with pytest.raises(ValueError):
        getattr(M, factory)(pretrained=True)


def test_densenet_channel_math():
    from paddle_tpu.vision.models import DenseNet

    with pytest.raises(ValueError):
        DenseNet(layers=123)
    net = DenseNet(layers=121, num_classes=4)
    net.eval()
    assert forward_shapes(net, 1, 3, 32, 32) == [[1, 4]]
