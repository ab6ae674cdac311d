"""Detection zoo (YOLO/FasterRCNN, static shapes), MoE, SEP utils, padded
NMS, native C++ pipeline kernels."""

import os
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.optimizer as opt


def _gt():
    gtb = np.zeros((2, 5, 4), dtype="float32")
    gtl = np.full((2, 5), -1, dtype="int64")
    gtb[0, 0] = [10, 10, 60, 60]
    gtl[0, 0] = 3
    gtb[1, 0] = [30, 40, 100, 110]
    gtl[1, 0] = 1
    return paddle.to_tensor(gtb), paddle.to_tensor(gtl)


@pytest.mark.slow  # full-detector train loops (~30-50s each on the CI
def test_yolo_trains_and_evals():  # mesh); tier-1 keeps the cheap shape/
    from paddle_tpu.vision.models import yolov3  # loss/backbone coverage

    rng = np.random.RandomState(0)
    img = paddle.to_tensor(rng.randn(2, 3, 128, 128).astype("float32"))
    gt_boxes, gt_labels = _gt()
    paddle.seed(0)
    m = yolov3(num_classes=5, depth=18)
    o = opt.Adam(learning_rate=1e-4, parameters=m.parameters())
    l0 = None
    for _ in range(3):
        out = m(img, gt_boxes, gt_labels)
        out["loss"].backward()
        o.step()
        o.clear_grad()
        l0 = l0 if l0 is not None else float(out["loss"])
    assert float(out["loss"]) < l0
    m.eval()
    dets = m(img)
    assert len(dets) == 2
    assert dets[0]["boxes"].shape[1] == 4
    assert dets[0]["valid"].numpy().dtype == bool


@pytest.mark.slow
def test_faster_rcnn_trains_and_evals():
    from paddle_tpu.vision.models import faster_rcnn

    rng = np.random.RandomState(1)
    img = paddle.to_tensor(rng.randn(2, 3, 128, 128).astype("float32"))
    gt_boxes, gt_labels = _gt()
    paddle.seed(1)
    m = faster_rcnn(num_classes=5, depth=18, num_proposals=32)
    o = opt.Adam(learning_rate=1e-4, parameters=m.parameters())
    l0 = None
    for _ in range(3):
        out = m(img, gt_boxes, gt_labels)
        out["loss"].backward()
        o.step()
        o.clear_grad()
        l0 = l0 if l0 is not None else float(out["loss"])
    assert float(out["loss"]) < l0
    m.eval()
    dets = m(img)
    assert len(dets) == 2


def test_nms_padded_traceable():
    from paddle_tpu.vision import ops as vops

    boxes = paddle.to_tensor(np.array(
        [[0, 0, 10, 10], [1, 1, 11, 11], [50, 50, 60, 60]], dtype="float32"))
    scores = paddle.to_tensor(np.array([0.9, 0.8, 0.7], dtype="float32"))

    @paddle.jit.to_static
    def run(b, s):
        idx, valid = vops.nms_padded(b, s, iou_threshold=0.5, top_k=3)
        return idx, valid

    idx, valid = run(boxes, scores)
    iv, vv = idx.numpy(), valid.numpy()
    kept = set(iv[vv].tolist())
    assert kept == {0, 2}


def test_matrix_nms_decays_overlaps():
    from paddle_tpu.vision import ops as vops

    boxes = paddle.to_tensor(np.array(
        [[0, 0, 10, 10], [0.5, 0.5, 10.5, 10.5], [50, 50, 60, 60]],
        dtype="float32"))
    scores = paddle.to_tensor(np.array(
        [[0.9, 0.85, 0.7]], dtype="float32"))  # one class (background=-1)
    out, num = vops.matrix_nms(boxes, scores, score_threshold=0.1,
                               keep_top_k=3, background_label=-1)
    a = out.numpy()
    assert a.shape[1] == 6  # [label, score, x1, y1, x2, y2]
    by_score = {tuple(r[2:4]): r[1] for r in a}
    assert by_score[(0.0, 0.0)] == pytest.approx(0.9, abs=1e-5)
    # heavily-overlapping second box MUST decay well below its raw 0.85
    assert by_score[(0.5, 0.5)] < 0.5
    # isolated third box keeps its score
    assert by_score[(50.0, 50.0)] == pytest.approx(0.7, abs=1e-5)
    # background_label=0 with a single class yields an empty result, not a crash
    empty, n0 = vops.matrix_nms(boxes, scores, score_threshold=0.1,
                                background_label=0)
    assert empty.shape[0] == 0 and int(n0.numpy()[0]) == 0


def test_nms_padded_negative_coords_classes():
    from paddle_tpu.vision import ops as vops

    # two DIFFERENT classes, overlapping coords incl. negatives: no
    # cross-class suppression allowed
    boxes = paddle.to_tensor(np.array(
        [[-5, -5, 10, 10], [-5, -5, 10, 10]], dtype="float32"))
    scores = paddle.to_tensor(np.array([0.9, 0.8], dtype="float32"))
    cats = paddle.to_tensor(np.array([0, 1], dtype="int64"))
    idx, valid = vops.nms_padded(boxes, scores, 0.5, top_k=2,
                                 category_idxs=cats)
    assert valid.numpy().sum() == 2


def test_native_collate():
    from paddle_tpu.io import native

    rng = np.random.RandomState(0)
    samples = [rng.randn(3, 5).astype("float32") for _ in range(7)]
    out = native.collate_f32(samples)
    np.testing.assert_array_equal(out, np.stack(samples))


def test_moe_layer_trains():
    from paddle_tpu.incubate.distributed.models.moe import MoELayer

    paddle.seed(0)
    moe = MoELayer(d_model=16, d_hidden=32, num_experts=4, top_k=2)
    x = paddle.to_tensor(np.random.RandomState(0).randn(2, 8, 16).astype("float32"))
    y = moe(x)
    assert y.shape == [2, 8, 16]
    assert np.isfinite(float(moe.aux_loss))

    head = nn.Linear(16, 4)
    params = moe.parameters() + head.parameters()
    o = opt.AdamW(learning_rate=1e-3, parameters=params)
    yl = paddle.to_tensor(np.random.RandomState(1).randint(0, 4, (2,)).astype("int64"))
    lossf = nn.CrossEntropyLoss()
    losses = []
    for _ in range(5):
        l = lossf(head(moe(x).mean(axis=1)), yl) + moe.aux_loss * 0.01
        l.backward()
        o.step()
        o.clear_grad()
        losses.append(float(l))
    assert losses[-1] < losses[0]


def test_moe_ep_sharding_under_mesh():
    from paddle_tpu.distributed import topology as topo
    from paddle_tpu.distributed.fleet.meta_parallel import MoELayer

    t = topo.CommunicateTopology(["dp", "mp"], [2, 4])
    topo.set_hybrid_communicate_group(topo.HybridCommunicateGroup(t))
    try:
        paddle.seed(1)
        moe = MoELayer(16, 32, num_experts=4)
        assert "mp" in str(moe.w1._value.sharding.spec)
        x = paddle.to_tensor(np.random.RandomState(0).randn(2, 8, 16).astype("float32"))
        y = moe(x)
        assert y.shape == [2, 8, 16]
    finally:
        topo.set_hybrid_communicate_group(None)


def test_sep_alltoall_manual_roundtrip():
    from paddle_tpu.distributed.fleet.meta_parallel import sep_utils
    from jax.sharding import Mesh, PartitionSpec as P

    B, S, H, D = 2, 16, 4, 8
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(B, S, H, D).astype("float32"))
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("sep",))

    def body(v):
        heads = sep_utils.alltoall_seq_to_heads(v, axis="sep")
        assert heads.shape == (B, S, H // 4, D)  # full seq, local heads
        return sep_utils.alltoall_heads_to_seq(heads, axis="sep")

    f = jax.jit(jax.shard_map(body, mesh=mesh,
                              in_specs=P(None, "sep"), out_specs=P(None, "sep"),
                              check_vma=False))
    out = f(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x), rtol=1e-6)


@pytest.mark.slow
def test_sep_attention_matches_plain():
    from paddle_tpu.distributed.fleet.meta_parallel import sep_attention
    from paddle_tpu.distributed import topology as topo
    import paddle_tpu.nn.functional as F

    t = topo.CommunicateTopology(["sep"], [4])
    topo.set_hybrid_communicate_group(topo.HybridCommunicateGroup(t))
    try:
        rng = np.random.RandomState(0)
        q = paddle.to_tensor(rng.randn(2, 16, 4, 8).astype("float32") * 0.5)
        out = sep_attention(q, q, q, is_causal=True, training=False)
        ref = F.scaled_dot_product_attention(q, q, q, is_causal=True,
                                             training=False)
        np.testing.assert_allclose(out.numpy(), ref.numpy(), rtol=2e-5, atol=2e-6)
    finally:
        topo.set_hybrid_communicate_group(None)


def test_native_pipeline_kernels():
    from paddle_tpu.io import native

    rng = np.random.RandomState(0)
    imgs = rng.randint(0, 256, (4, 32, 32, 3), dtype=np.uint8)
    mean = np.array([123.7, 116.3, 103.5], np.float32)
    std = np.array([58.4, 57.1, 57.4], np.float32)
    flips = np.array([0, 1, 0, 1], np.uint8)
    out = native.normalize_chw(imgs, mean, std, flips)
    x = imgs.astype(np.float32)
    x[flips.astype(bool)] = x[flips.astype(bool), :, ::-1]
    ref = ((x - mean) / std).transpose(0, 3, 1, 2)
    np.testing.assert_allclose(out, ref, rtol=1e-6, atol=1e-5)

    ys = np.array([0, 1, 2, 3], np.int32)
    xs = np.array([3, 2, 1, 0], np.int32)
    crop = native.crop_batch(imgs, ys, xs, 16, 16)
    np.testing.assert_array_equal(crop[2], imgs[2, 2:18, 1:17])


# ================================================== PP-YOLOE proper (r3)
@pytest.mark.slow
def test_cspresnet_backbone_and_pan():
    from paddle_tpu.vision.models.cspresnet import CSPRepResNet, CustomCSPPAN

    paddle.seed(0)
    bb = CSPRepResNet(layers=(1, 1, 1, 1), channels=(16, 16, 32, 64, 128))
    x = paddle.to_tensor(np.random.RandomState(0).randn(1, 3, 64, 64)
                         .astype("float32"))
    feats = bb(x)
    assert [tuple(f.shape) for f in feats] == \
        [(1, 32, 8, 8), (1, 64, 4, 4), (1, 128, 2, 2)]
    neck = CustomCSPPAN(bb.out_channels, out_channels=(48, 32, 24), block_num=1)
    outs = neck(feats)
    # finest-first, matching head strides (8, 16, 32)
    assert [tuple(o.shape) for o in outs] == \
        [(1, 24, 8, 8), (1, 32, 4, 4), (1, 48, 2, 2)]


def test_repvgg_fusion_exact():
    """Re-parameterized single 3x3 conv must equal the dual-branch form."""
    from paddle_tpu.vision.models.cspresnet import RepVggBlock

    paddle.seed(1)
    blk = RepVggBlock(8, 8, act="relu").eval()
    x = paddle.to_tensor(np.random.RandomState(1).randn(2, 8, 16, 16)
                         .astype("float32"))
    y0 = blk(x).numpy()
    blk.convert_to_deploy()
    y1 = blk(x).numpy()
    np.testing.assert_allclose(y0, y1, rtol=1e-5, atol=1e-5)


def test_varifocal_loss_formula():
    from paddle_tpu.vision.models.detection import varifocal_loss
    import jax.numpy as jnp

    rs = np.random.RandomState(0)
    logits = rs.randn(6, 3).astype("float32")
    q = np.zeros((6, 3), "float32")
    lab = np.zeros((6, 3), "float32")
    q[0, 1] = 0.7
    lab[0, 1] = 1.0
    got = np.asarray(varifocal_loss(jnp.asarray(logits), jnp.asarray(q),
                                    jnp.asarray(lab), alpha=0.75, gamma=2.0))
    p = 1 / (1 + np.exp(-logits))
    bce = -(q * np.log(p) + (1 - q) * np.log(1 - p))
    w = 0.75 * p ** 2 * (1 - lab) + q * lab
    np.testing.assert_allclose(got, bce * w, rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_ppyoloe_trains_and_evals():
    from paddle_tpu.vision.models.detection import ppyoloe

    paddle.seed(0)
    m = ppyoloe(num_classes=4, size="s")
    img = paddle.to_tensor(np.random.RandomState(0).randn(2, 3, 64, 64)
                           .astype("float32"))
    gtb = np.zeros((2, 5, 4), "float32")
    gtl = np.full((2, 5), -1, "int64")
    gtb[0, 0] = [8, 8, 40, 40]; gtl[0, 0] = 1
    gtb[1, 0] = [16, 16, 56, 56]; gtl[1, 0] = 3
    opt_ = opt.Adam(learning_rate=1e-3, parameters=m.parameters())
    step = paddle.jit.TrainStep(m, opt_)  # dict-loss model, no loss_fn
    batch = {"img": img, "gt_boxes": paddle.to_tensor(gtb),
             "gt_labels": paddle.to_tensor(gtl)}
    losses = [float(step(batch)) for _ in range(3)]
    assert losses[-1] < losses[0], losses
    m.eval()
    res = m(img)
    assert res[0]["boxes"].shape[1] == 4
    # deploy-time rep fusion keeps eval outputs (scores) close
    s0 = res[0]["scores"].numpy()
    m.convert_to_deploy()
    s1 = m(img)[0]["scores"].numpy()
    np.testing.assert_allclose(s0, s1, rtol=1e-3, atol=1e-4)


@pytest.mark.slow
def test_ppyoloe_loss_on_non_divisible_input():
    """Centers must come from the REAL conv grid, not img_size//stride
    (they differ when H,W aren't divisible by 32)."""
    from paddle_tpu.vision.models.detection import ppyoloe

    paddle.seed(2)
    m = ppyoloe(num_classes=2, size="s")
    img = paddle.to_tensor(np.random.RandomState(0).randn(1, 3, 100, 100)
                           .astype("float32"))
    gtb = np.zeros((1, 3, 4), "float32")
    gtl = np.full((1, 3), -1, "int64")
    gtb[0, 0] = [10, 10, 60, 60]; gtl[0, 0] = 1
    losses = m(img, paddle.to_tensor(gtb), paddle.to_tensor(gtl))
    assert np.isfinite(float(losses["loss"]))


def test_rcnn_delta_coder_roundtrip():
    """Standard (dx,dy,dw,dh) bbox coder: encode(decode) is the identity
    and matches the reference weights (10,10,5,5)."""
    from paddle_tpu.vision.models.detection import (_decode_deltas,
                                                    _encode_deltas)

    rs = np.random.RandomState(0)
    raw = rs.uniform(0, 50, (6, 4)).astype("float32")
    p = np.concatenate([np.minimum(raw[:, :2], raw[:, 2:]),
                        np.maximum(raw[:, :2], raw[:, 2:]) + 4], -1)
    g = p + np.float32([3., -2., 5., 1.])
    d = _encode_deltas(jnp.asarray(p), jnp.asarray(g))
    rec = _decode_deltas(jnp.asarray(p), d)
    np.testing.assert_allclose(np.asarray(rec), g, rtol=1e-4, atol=1e-3)
    # known value: gt shifted +10 in x on a 20-wide box -> dx = 10*10/20 = 5
    p1 = jnp.asarray([[0.0, 0.0, 20.0, 10.0]])
    g1 = jnp.asarray([[10.0, 0.0, 30.0, 10.0]])
    np.testing.assert_allclose(np.asarray(_encode_deltas(p1, g1))[0],
                               [5.0, 0.0, 0.0, 0.0], atol=1e-5)


@pytest.mark.slow
def test_rcnn_class_specific_regression_shapes():
    from paddle_tpu.vision.models import faster_rcnn

    paddle.seed(2)
    m = faster_rcnn(num_classes=3, depth=18, num_proposals=16)
    assert m.bbox_delta.weight.shape[-1] == 12  # 4 deltas per class
    img = paddle.to_tensor(
        np.random.RandomState(0).randn(1, 3, 96, 96).astype("float32"))
    m.eval()
    dets = m(img)
    assert dets[0]["boxes"].shape == [16, 4]
    assert int(dets[0]["labels"].numpy().max()) < 3


def test_native_pipeline_thread_safety_and_determinism():
    """SURVEY §5.2 race/determinism check for the native C++ kernels:
    hammer normalize/crop/collate from many Python threads concurrently and
    at several internal thread counts — results must be bit-identical to
    the single-threaded reference on every call."""
    import concurrent.futures as cf

    from paddle_tpu.io import native

    rng = np.random.RandomState(0)
    imgs = rng.randint(0, 256, (8, 24, 24, 3), dtype=np.uint8)
    mean = np.array([123.7, 116.3, 103.5], np.float32)
    std = np.array([58.4, 57.1, 57.4], np.float32)
    flips = (rng.rand(8) > 0.5).astype(np.uint8)
    ref_norm = native.normalize_chw(imgs, mean, std, flips, num_threads=1)
    ys = rng.randint(0, 8, 8).astype(np.int32)
    xs = rng.randint(0, 8, 8).astype(np.int32)
    ref_crop = native.crop_batch(imgs, ys, xs, 16, 16, num_threads=1)
    samples = [rng.randn(5, 7).astype(np.float32) for _ in range(16)]
    ref_coll = native.collate_f32(samples, num_threads=1)

    def hammer(i):
        nt = (i % 4)  # 0 = library default, 1..3 explicit
        a = native.normalize_chw(imgs, mean, std, flips, num_threads=nt)
        b = native.crop_batch(imgs, ys, xs, 16, 16, num_threads=nt)
        c = native.collate_f32(samples, num_threads=nt)
        np.testing.assert_array_equal(a, ref_norm)
        np.testing.assert_array_equal(b, ref_crop)
        np.testing.assert_array_equal(c, ref_coll)
        return True

    with cf.ThreadPoolExecutor(max_workers=8) as ex:
        assert all(ex.map(hammer, range(64)))


def test_native_pipeline_under_tsan():
    """Run the native kernels in a subprocess built with -fsanitize=thread
    and LD_PRELOAD'd libtsan — any data race aborts the worker (SURVEY §5.2:
    the reference gates its threaded runtime on TSAN CI)."""
    import glob
    import subprocess
    import sys

    libtsan = sorted(glob.glob("/usr/lib/gcc/x86_64-linux-gnu/*/libtsan.so"))
    if not libtsan:
        pytest.skip("libtsan not available")
    worker = r"""
import importlib.util
import os
import numpy as np
# load native.py standalone: the full package would initialize jax, which
# is not TSAN-instrumented; the native module is dependency-free
spec = importlib.util.spec_from_file_location(
    "pt_native", os.environ["PT_NATIVE_PATH"])
native = importlib.util.module_from_spec(spec)
spec.loader.exec_module(native)
assert native.available(), "native lib failed to build under TSAN"
rng = np.random.RandomState(0)
imgs = rng.randint(0, 256, (8, 24, 24, 3), dtype=np.uint8)
mean = np.array([123.7, 116.3, 103.5], np.float32)
std = np.array([58.4, 57.1, 57.4], np.float32)
for nt in (0, 2, 4):
    native.normalize_chw(imgs, mean, std, None, num_threads=nt)
    native.crop_batch(imgs, np.zeros(8, np.int32), np.zeros(8, np.int32),
                      16, 16, num_threads=nt)
    native.collate_f32([rng.randn(5, 7).astype(np.float32)
                        for _ in range(16)], num_threads=nt)
print("TSAN_CLEAN")
"""
    env = dict(os.environ)
    env["PADDLE_TPU_NATIVE_TSAN"] = "1"
    env["LD_PRELOAD"] = libtsan[0]
    env["TSAN_OPTIONS"] = "exitcode=66"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo
    env["PT_NATIVE_PATH"] = os.path.join(repo, "paddle_tpu", "io", "native.py")
    r = subprocess.run([sys.executable, "-c", worker], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0 and "TSAN_CLEAN" in r.stdout, \
        f"rc={r.returncode}\n{r.stdout}\n{r.stderr[-3000:]}"


def test_detection_map_metric():
    """VOC mAP: hand-computed PR curves for both AP rules, padding-aware
    gt, greedy one-match-per-gt, and end-to-end consumption of a
    detector's padded eval output."""
    from paddle_tpu.metric import DetectionMAP

    gt = np.array([[0, 0, 10, 10], [20, 20, 30, 30]], "float32")
    gl = np.array([0, 0])
    det = np.array([[0, 0, 10, 10], [50, 50, 60, 60], [20, 20, 30, 30]],
                   "float32")
    sc = np.array([0.9, 0.8, 0.7])
    lb = np.array([0, 0, 0])

    m = DetectionMAP(num_classes=1, map_type="integral")
    m.update(det, sc, lb, gt, gl)
    np.testing.assert_allclose(m.accumulate(), 0.5 + 0.5 * 2 / 3, rtol=1e-6)

    m11 = DetectionMAP(num_classes=1, map_type="11point")
    m11.update(det, sc, lb, gt, gl)
    np.testing.assert_allclose(m11.accumulate(), (6 + 5 * 2 / 3) / 11,
                               rtol=1e-6)

    # duplicate hits on one gt count as FP; padded gt rows (label -1) ignored
    m2 = DetectionMAP(num_classes=2, map_type="integral")
    gt_pad = np.array([[0, 0, 10, 10], [0, 0, 0, 0]], "float32")
    gl_pad = np.array([0, -1])
    m2.update(np.array([[0, 0, 10, 10], [1, 1, 10, 10]], "float32"),
              np.array([0.9, 0.8]), np.array([0, 0]), gt_pad, gl_pad)
    np.testing.assert_allclose(m2.accumulate(), 1.0, rtol=1e-6)  # TP then FP

    # end-to-end: detector padded eval output feeds straight in
    from paddle_tpu.vision.models import ppyoloe

    paddle.seed(0)
    model = ppyoloe(num_classes=2, size="s")
    model.eval()
    img = paddle.to_tensor(
        np.random.RandomState(0).randn(1, 3, 64, 64).astype("float32"))
    res = model(img)[0]
    meval = DetectionMAP(num_classes=2, map_type="integral")
    meval.update(res["boxes"], res["scores"], res["labels"],
                 np.array([[8, 8, 40, 40]], "float32"), np.array([1]),
                 valid=res["valid"])
    assert 0.0 <= meval.accumulate() <= 1.0


def test_detection_map_difficult_gt():
    """VOC semantics: difficult gts don't count toward recall, and
    matching one is neither TP nor FP (evaluate_difficult=False)."""
    from paddle_tpu.metric import DetectionMAP

    gt = np.array([[0, 0, 10, 10], [20, 20, 30, 30]], "float32")
    gl = np.array([0, 0])
    diff = np.array([False, True])
    det = np.array([[0, 0, 10, 10], [20, 20, 30, 30]], "float32")
    sc = np.array([0.9, 0.8])
    lb = np.array([0, 0])

    m = DetectionMAP(num_classes=1, map_type="integral",
                     evaluate_difficult=False)
    m.update(det, sc, lb, gt, gl, gt_difficult=diff)
    # only the non-difficult gt counts: 1 TP / 1 gt, difficult match ignored
    np.testing.assert_allclose(m.accumulate(), 1.0, rtol=1e-6)

    m2 = DetectionMAP(num_classes=1, map_type="integral",
                      evaluate_difficult=True)
    m2.update(det, sc, lb, gt, gl, gt_difficult=diff)
    np.testing.assert_allclose(m2.accumulate(), 1.0, rtol=1e-6)  # both TPs
