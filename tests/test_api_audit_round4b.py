"""Round-4 API audit, second sweep: static legacy surface, sequence/CRF
ops, text datasets + Viterbi, vision models/transforms/ops, incubate
segment/graph ops, fleet role makers, utils/device/jit shims."""
import itertools

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
import paddle_tpu.static as static
from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.utils import unique_name

from tests.op_test import forward_shapes

rng = np.random.RandomState(0)


def t(x):
    return Tensor(np.asarray(x))


# -- viterbi / CRF -----------------------------------------------------------

def test_viterbi_decode_matches_brute_force():
    B, L, N = 2, 4, 3
    pot = rng.randn(B, L, N).astype(np.float32)
    trans = rng.randn(N, N).astype(np.float32)
    lens = np.array([4, 3])
    scores, paths = paddle.text.viterbi_decode(
        t(pot), t(trans), t(lens), include_bos_eos_tag=False)
    for b in range(B):
        best, bestp = -1e9, None
        for p in itertools.product(range(N), repeat=int(lens[b])):
            s = pot[b, 0, p[0]] + sum(
                trans[p[i - 1], p[i]] + pot[b, i, p[i]]
                for i in range(1, len(p)))
            if s > best:
                best, bestp = s, p
        assert abs(best - scores.numpy()[b]) < 1e-4
        assert list(paths.numpy()[b][:int(lens[b])]) == list(bestp)


def test_viterbi_decoder_class_and_crf_decoding():
    B, L, N = 2, 5, 4
    pot = rng.randn(B, L, N + 2).astype(np.float32)
    trans = rng.randn(N + 2, N + 2).astype(np.float32)
    lens = np.array([5, 4])
    dec = paddle.text.ViterbiDecoder(t(trans))
    scores, paths = dec(t(pot), t(lens))
    assert paths.shape == [B, L]
    assert (paths.numpy() < N).all()  # BOS/EOS never emitted
    with unique_name.guard():
        path2 = static.nn.crf_decoding(t(pot), length=t(lens),
                                       transition=t(trans))
    np.testing.assert_array_equal(path2.numpy(), paths.numpy())


# -- static legacy surface ---------------------------------------------------

def test_static_legacy_layers_eager():
    with unique_name.guard():
        paddle.seed(0)
        img = t(rng.randn(2, 3, 8, 8).astype(np.float32))
        y = static.nn.conv2d(img, 4, 3, padding=1, act="relu")
        assert list(y.shape) == [2, 4, 8, 8]
        z = static.nn.batch_norm(y)
        assert list(z.shape) == [2, 4, 8, 8]
        e = static.nn.embedding(t(rng.randint(0, 10, (2, 5))), (10, 6))
        assert list(e.shape) == [2, 5, 6]
        n = static.nn.layer_norm(t(rng.randn(3, 7).astype(np.float32)))
        assert list(n.shape) == [3, 7]
        w = t(rng.randn(6, 4).astype(np.float32))
        sn = static.nn.spectral_norm(w, power_iters=20)
        s = np.linalg.svd(sn.numpy(), compute_uv=False)[0]
        assert abs(s - 1.0) < 1e-3


def test_static_nce_and_case():
    with unique_name.guard():
        paddle.seed(0)
        x = t(rng.randn(6, 8).astype(np.float32))
        y = t(rng.randint(0, 20, (6,)))
        loss = static.nn.nce(x, y, 20, num_neg_samples=3)
        assert list(loss.shape) == [6, 1]
        assert np.isfinite(loss.numpy()).all()

    out = static.nn.case(
        [(t(np.array(False)), lambda: t(np.array(1.0))),
         (t(np.array(True)), lambda: t(np.array(2.0)))],
        default=lambda: t(np.array(3.0)))
    assert float(out.numpy()) == 2.0


def test_static_sequence_ops_dense_contract():
    x = t(rng.randn(2, 5, 3).astype(np.float32))
    lens = t(np.array([5, 3]))
    pooled = static.nn.sequence_pool(x, "average", length=lens)
    want = x.numpy()[1, :3].mean(axis=0)
    np.testing.assert_allclose(pooled.numpy()[1], want, rtol=1e-5)
    last = static.nn.sequence_last_step(x, lens)
    np.testing.assert_allclose(last.numpy()[1], x.numpy()[1, 2])
    rev = static.nn.sequence_reverse(x, length=lens)
    np.testing.assert_allclose(rev.numpy()[1, :3], x.numpy()[1, 2::-1])
    np.testing.assert_allclose(rev.numpy()[1, 3:], x.numpy()[1, 3:])
    sm = static.nn.sequence_softmax(x, length=lens)
    np.testing.assert_allclose(sm.numpy()[1, :, 0].sum(), 1.0, rtol=1e-5)
    assert abs(sm.numpy()[1, 3:, 0].sum()) < 1e-6


def test_static_rnn_runs():
    with unique_name.guard():
        paddle.seed(0)
        seq = t(rng.randn(4, 2, 8).astype(np.float32))  # [T, B, F]
        rnn = static.StaticRNN() if hasattr(static, "StaticRNN") \
            else static.nn.StaticRNN()
        xin = rnn.step_input(seq)
        h = rnn.memory(init=t(np.zeros((2, 8), np.float32)))
        lin = paddle.nn.Linear(16, 8)

        def step(tstep):
            import paddle_tpu.ops as ops

            nh = paddle.tanh(lin(ops.concat([xin.value(), h._slot["cur"]],
                                            axis=-1)))
            rnn.update_memory(h, nh)
            rnn.step_output(nh)

        out = rnn.run(step)
    assert list(out.shape) == [4, 2, 8]


def test_static_compat_metrics_ema_state():
    logits = t(rng.randn(8, 5).astype(np.float32))
    label = t(rng.randint(0, 5, (8, 1)))
    acc = static.accuracy(logits, label, k=5)
    assert float(acc.numpy()) == 1.0
    scores = t(np.array([[0.9, 0.1], [0.2, 0.8], [0.3, 0.7], [0.6, 0.4]],
                        np.float32))
    y = t(np.array([[0], [1], [1], [0]]))
    a = static.auc(scores, y)
    assert float(a.numpy()) == 1.0  # perfectly ranked

    with unique_name.guard():
        paddle.seed(0)
        lin = paddle.nn.Linear(4, 2)
        main = static.Program()
        with static.program_guard(main):
            xv = static.data("x", [2, 4], "float32")
            out = lin(xv)
        ema = static.ExponentialMovingAverage(0.5)
        w0 = np.asarray(lin.weight._value).copy()
        ema.update(lin.parameters())       # shadow = w0
        lin.weight._value = lin.weight._value + 1.0
        ema.update(lin.parameters())       # shadow = w0 + 0.5
        with ema.apply():
            applied = np.asarray(lin.weight._value)
        after = np.asarray(lin.weight._value)
        np.testing.assert_allclose(after, w0 + 1.0)
        np.testing.assert_allclose(applied, w0 + 0.5, rtol=1e-5)

        state = {p.name: np.asarray(p._value) * 0.0
                 for p in main.all_parameters()}
        assert static.set_program_state(main, state) >= 1
        assert np.allclose(np.asarray(lin.weight._value), 0.0)


def test_static_places_and_guards(tmp_path):
    assert len(static.cpu_places(2)) == 2
    assert static.cuda_places([0])
    with static.device_guard("cpu"):
        pass
    with pytest.raises(ValueError):
        static.device_guard("fpga").__enter__()
    ps = static.ParallelExecutor()
    assert ps is not None
    v = static.create_global_var([2, 2], 1.5, "float32")
    assert np.allclose(v.numpy(), 1.5)
    with unique_name.guard():
        p = static.create_parameter([3, 3], "float32")
        assert list(p.shape) == [3, 3]


# -- text / incubate ---------------------------------------------------------

def test_text_datasets_shapes():
    for cls in (paddle.text.Conll05st, paddle.text.Imikolov,
                paddle.text.Movielens, paddle.text.WMT14, paddle.text.WMT16):
        ds = cls()
        assert len(ds) > 0
        item = ds[0]
        assert isinstance(item, tuple)


def test_incubate_segment_and_graph_ops():
    inc = paddle.incubate
    d = t(np.arange(12, dtype=np.float32).reshape(6, 2))
    ids = t(np.array([0, 0, 1, 1, 1, 2]))
    np.testing.assert_allclose(inc.segment_sum(d, ids).numpy()[0], [2, 4])
    np.testing.assert_allclose(inc.segment_mean(d, ids).numpy()[1], [6, 7])
    np.testing.assert_allclose(inc.segment_max(d, ids).numpy()[2], [10, 11])
    np.testing.assert_allclose(inc.segment_min(d, ids).numpy()[1], [4, 5])

    x = t(np.eye(3, dtype=np.float32))
    out = inc.graph_send_recv(x, t(np.array([0, 1, 2, 0])),
                              t(np.array([1, 2, 0, 2])), "sum")
    np.testing.assert_allclose(out.numpy()[2], [1, 1, 0])

    src, dst, nodes = inc.graph_reindex(
        t(np.array([5, 9])), t(np.array([9, 7, 5, 3])),
        t(np.array([2, 2])))
    assert nodes.numpy().tolist() == [5, 9, 7, 3]
    assert dst.numpy().tolist() == [0, 0, 1, 1]

    # CSC graph: node 0 <- {1, 2}, node 1 <- {0}, node 2 <- {}
    row = t(np.array([1, 2, 0]))
    colptr = t(np.array([0, 2, 3, 3]))
    neigh, cnt = inc.graph_sample_neighbors(row, colptr,
                                            t(np.array([0, 2])),
                                            sample_size=-1)
    assert cnt.numpy().tolist() == [2, 0]
    assert sorted(neigh.numpy().tolist()) == [1, 2]

    # advisor regression: duplicate centers must map dst through the
    # first-seen order table, not positional arange
    src2, dst2, nodes2 = inc.graph_reindex(
        t(np.array([5, 5, 9])), t(np.array([9, 7, 5, 3])),
        t(np.array([1, 1, 2])))
    assert nodes2.numpy().tolist() == [5, 9, 7, 3]
    assert dst2.numpy().tolist() == [0, 0, 1, 1]

    sm = inc.softmax_mask_fuse_upper_triangle(
        t(np.zeros((1, 1, 4, 4), np.float32)))
    np.testing.assert_allclose(sm.numpy()[0, 0, 0], [1, 0, 0, 0])
    assert float(inc.identity_loss(t(np.array([2.0, 4.0])),
                                   "mean").numpy()) == 3.0
    # advisor regression: integer reduction codes are 0=sum, 1=mean, 2=none
    assert float(inc.identity_loss(t(np.array([2.0, 4.0])), 0).numpy()) == 6.0
    assert float(inc.identity_loss(t(np.array([2.0, 4.0])), 1).numpy()) == 3.0
    assert inc.identity_loss(t(np.array([2.0, 4.0])),
                             2).numpy().tolist() == [2.0, 4.0]


# -- fleet role makers / misc ------------------------------------------------

def test_fleet_role_maker_and_util(monkeypatch):
    from paddle_tpu.distributed import fleet

    monkeypatch.setenv("PADDLE_TRAINER_ID", "1")
    monkeypatch.setenv("PADDLE_TRAINERS_NUM", "4")
    rm = fleet.PaddleCloudRoleMaker(is_collective=True)
    assert rm.worker_index() == 1 and rm.worker_num() == 4
    util = fleet.UtilBase()
    shard = util.get_file_shard([f"f{i}" for i in range(10)])
    assert shard == ["f3", "f4", "f5"]  # rank 1 of 4 over 10 files

    gen = _Gen()
    rows = gen.run_from_memory(["a b", "c"])
    assert rows == ["words 2 a b", "words 1 c"]


class _Gen:
    pass


from paddle_tpu.distributed.fleet import MultiSlotStringDataGenerator  # noqa: E402


class _Gen(MultiSlotStringDataGenerator):  # noqa: F811
    def generate_sample(self, line):
        def gen():
            yield [("words", line.split())]

        return gen


# -- vision ------------------------------------------------------------------

@pytest.mark.parametrize("factory,hw,n_out", [
    ("shufflenet_v2_x0_25", 64, 1),
    ("googlenet", 96, 3),       # out and the two auxiliary heads
])
def test_vision_new_models_forward(factory, hw, n_out, monkeypatch):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.vision import models as M

    # a shape does not depend on the weights' values: the normal family
    # draws zeros here. XLA:CPU takes ~0.4 s to compile jax.random.normal
    # for each new shape, and googlenet has 49 of them.
    monkeypatch.setattr(jax.random, "normal",
                        lambda key, shape=(), dtype=np.float32:
                        jnp.zeros(shape, dtype))

    with unique_name.guard():
        paddle.seed(0)
        m = getattr(M, factory)(num_classes=7)
        m.eval()
        assert forward_shapes(m, 1, 3, hw, hw) == [[1, 7]] * n_out


def test_vision_resnext_constructs():
    from paddle_tpu.vision import models as M

    with unique_name.guard():
        paddle.seed(0)
        r = M.resnext101_32x4d(num_classes=7)
        assert r is not None  # construction exercises the grouped blocks


def test_vision_functional_transforms():
    from paddle_tpu.vision import transforms as T

    img = (rng.rand(12, 16, 3) * 255).astype(np.uint8)
    assert T.hflip(img).shape == img.shape
    np.testing.assert_array_equal(T.hflip(T.hflip(img)), img)
    assert T.center_crop(img, 8).shape == (8, 8, 3)
    assert T.crop(img, 2, 3, 4, 5).shape == (4, 5, 3)
    assert T.pad(img, 2).shape == (16, 20, 3)
    b = T.adjust_brightness(img, 2.0)
    assert b.mean() >= img.mean()
    gray = T.to_grayscale(img)
    assert gray.shape == (12, 16, 1)
    rot = T.rotate(img, 90)
    assert rot.shape == img.shape
    aff = T.affine(img, 0, (0, 0), 1.0, 0.0)
    np.testing.assert_array_equal(aff, img)  # identity affine
    ident = T.perspective(img, [[0, 0], [15, 0], [15, 11], [0, 11]],
                          [[0, 0], [15, 0], [15, 11], [0, 11]])
    np.testing.assert_array_equal(ident, img)
    er = T.erase(img, 2, 2, 4, 4, 0)
    assert (np.asarray(er)[2:6, 2:6] == 0).all()
    hue = T.adjust_hue(img, 0.0)
    np.testing.assert_allclose(hue.astype(int), img.astype(int), atol=2)


def test_vision_ops_additions(tmp_path):
    from paddle_tpu.vision import ops as V

    x = t(rng.randn(1, 8, 16, 16).astype(np.float32))
    boxes = t(np.array([[0., 0., 8., 8.]], np.float32))
    bn = t(np.array([1], np.int32))
    assert list(V.RoIAlign(4)(x, boxes, bn).shape) == [1, 8, 4, 4]
    assert list(V.RoIPool(4)(x, boxes, bn).shape) == [1, 8, 4, 4]
    assert list(V.PSRoIPool(2)(x, boxes, bn).shape) == [1, 2, 2, 2]

    feat = t(rng.randn(2, 3 * 85, 4, 4).astype(np.float32))
    img = t(np.array([[128, 128], [128, 128]], np.int32))
    b, s = V.yolo_box(feat, img, [10, 13, 16, 30, 33, 23], 80, 0.01, 32)
    assert list(b.shape) == [2, 48, 4] and list(s.shape) == [2, 48, 80]
    bx = b.numpy()
    assert (bx >= 0).all() and (bx <= 127).all()  # clipped to image

    gtb = t((rng.rand(2, 5, 4) * 0.5 + 0.2).astype(np.float32))
    gtl = t(rng.randint(0, 80, (2, 5)))
    loss = V.yolo_loss(feat, gtb, gtl, [10, 13, 16, 30, 33, 23], [0, 1, 2],
                       80, 0.7, 32)
    assert list(loss.shape) == [2] and np.isfinite(loss.numpy()).all()

    from PIL import Image
    import io

    img_np = (rng.rand(8, 8, 3) * 255).astype(np.uint8)
    buf = io.BytesIO()
    Image.fromarray(img_np).save(buf, format="JPEG")
    p = str(tmp_path / "t.jpg")
    with open(p, "wb") as f:
        f.write(buf.getvalue())
    raw = V.read_file(p)
    dec = V.decode_jpeg(raw)
    assert list(dec.shape) == [3, 8, 8]


def test_jit_traced_layer(tmp_path):
    with unique_name.guard():
        paddle.seed(0)
        lin = paddle.nn.Linear(4, 2)
        x = t(rng.randn(2, 4).astype(np.float32))
        outs, traced = paddle.jit.TracedLayer.trace(lin, [x])
        assert list(outs.shape) == [2, 2]
        path = str(tmp_path / "traced")
        traced.save_inference_model(path)
        loaded = paddle.jit.load(path)
        np.testing.assert_allclose(np.asarray(loaded(x)._value),
                                   outs.numpy(), rtol=1e-5)
    paddle.jit.set_code_level(50)
    paddle.jit.set_verbosity(3)


def test_utils_helpers():
    paddle.utils.require_version("0.0.1")
    with pytest.raises(Exception):
        paddle.utils.require_version("999.0.0")
    assert paddle.utils.try_import("json") is not None
    with pytest.raises(ImportError):
        paddle.utils.try_import("definitely_not_a_module_xyz")

    calls = []

    @paddle.utils.deprecated(update_to="new_fn", since="2.0")
    def old_fn():
        calls.append(1)
        return 7

    with pytest.warns(DeprecationWarning):
        assert old_fn() == 7


# -- review-fix regressions --------------------------------------------------

def test_require_version_accepts_current_exact():
    from paddle_tpu.version import full_version

    paddle.utils.require_version(full_version)  # exact pin must pass


def test_data_norm_scale_shift_and_detached_stats():
    with unique_name.guard():
        x = t(rng.randn(8, 4).astype(np.float32))
        x.stop_gradient = False
        y = static.nn.data_norm(x, enable_scale_and_shift=True)
        assert list(y.shape) == [8, 4]
        y.sum().backward()
        assert np.isfinite(np.asarray(x.grad._value)).all()
    np.testing.assert_allclose(y.numpy().mean(0), 0.0, atol=1e-5)


def test_multi_box_head_locs_align_with_priors():
    with unique_name.guard():
        paddle.seed(0)
        feats = [t(rng.randn(1, 8, 4, 4).astype(np.float32)),
                 t(rng.randn(1, 8, 2, 2).astype(np.float32))]
        image = t(rng.randn(1, 3, 64, 64).astype(np.float32))
        locs, confs, boxes, variances = static.nn.multi_box_head(
            feats, image, base_size=64, num_classes=3,
            aspect_ratios=[[1.0, 2.0], [1.0, 2.0]], min_ratio=20,
            max_ratio=90, flip=True)
    # the row counts of predictions and priors MUST agree (review fix:
    # aspect ratio 1.0 was double-counted in the conv width)
    assert locs.shape[1] == boxes.shape[0] == variances.shape[0]
    assert confs.shape[1] == boxes.shape[0]


def test_yolo_loss_respects_ignore_thresh():
    feat_np = rng.randn(1, 3 * 15, 4, 4).astype(np.float32)
    gtb = t(np.array([[[0.5, 0.5, 0.4, 0.4]]], np.float32))
    gtl = t(np.array([[2]]))
    from paddle_tpu.vision import ops as V

    # permissive threshold ignores more negatives => loss can only shrink
    strict = float(V.yolo_loss(t(feat_np), gtb, gtl,
                               [10, 13, 16, 30, 33, 23], [0, 1, 2], 10,
                               0.99, 32).numpy()[0])
    loose = float(V.yolo_loss(t(feat_np), gtb, gtl,
                              [10, 13, 16, 30, 33, 23], [0, 1, 2], 10,
                              0.0, 32).numpy()[0])
    assert loose <= strict
