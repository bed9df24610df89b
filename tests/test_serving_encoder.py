"""Encoder scoring, the BERT serving path (``model.scorer``): parity with
the eager forward and one compile per sequence bucket. A file of its own:
under ``--dist loadfile`` a file is one worker's, and the eager reference
here was a third of ``tests/test_serving.py``'s time."""
import numpy as np

import paddle_tpu as paddle
from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.models import BertConfig, BertForSequenceClassification
from paddle_tpu.profiler import telemetry
from paddle_tpu.utils import unique_name

from tests.test_serving import _no_persistent_compile_cache  # noqa: F401


def test_encoder_scorer_parity_and_bucket_compiles(
        _no_persistent_compile_cache):
    with unique_name.guard():
        paddle.seed(0)
        model = BertForSequenceClassification(
            BertConfig(vocab_size=128, hidden_size=32, num_layers=2,
                       num_heads=2, intermediate_size=64,
                       max_position_embeddings=64, hidden_dropout=0.0,
                       attention_dropout=0.0),
            num_classes=3)
    model.eval()
    telemetry.reset()
    telemetry.enable()
    try:
        scorer = model.scorer(max_batch=4, seq_buckets=(8, 16))
        rng = np.random.RandomState(0)
        # inside and on the edge of either bucket, each twice: the eager
        # reference below compiles every op anew for each new length
        seqs = [rng.randint(0, 128, n).tolist()
                for n in (5, 8, 11, 16, 5, 8)]
        got = scorer.score(seqs)
        counts = telemetry.get_telemetry().compile_counts()
    finally:
        telemetry.disable()
        telemetry.reset()
    assert got.shape == (6, 3)
    assert counts.get("serve_score") == 2, counts  # one per bucket
    for s, row in zip(seqs, got):
        want = np.asarray(model(Tensor(np.asarray(s, np.int64)[None]))
                          ._value)[0]
        np.testing.assert_allclose(row, want, rtol=1e-4, atol=1e-5)
