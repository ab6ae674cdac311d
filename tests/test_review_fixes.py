"""Regression tests for review findings (norm bias, dropout infer-scale,
reversed-RNN masking, conv_transpose output_size, per-group functional
update, OneCycleLR three_phase, bicubic align_corners)."""

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu import nn


def test_batch_norm_bias_without_weight():
    bn = nn.BatchNorm2D(3, weight_attr=False)
    bn.bias.set_value(np.full(3, 2.0, dtype="float32"))
    bn.eval()
    x = paddle.to_tensor(np.zeros((1, 3, 2, 2), dtype="float32"))
    out = bn(x)
    np.testing.assert_allclose(out.numpy(), 2.0, atol=1e-5)


def test_layer_norm_bias_without_weight():
    x = paddle.to_tensor(np.random.RandomState(0).rand(2, 4).astype("float32"))
    bias = paddle.to_tensor(np.full(4, 1.5, dtype="float32"))
    out = F.layer_norm(x, 4, weight=None, bias=bias)
    ref = F.layer_norm(x, 4)
    np.testing.assert_allclose(out.numpy(), ref.numpy() + 1.5, atol=1e-5)


def test_dropout_downscale_in_infer():
    x = paddle.to_tensor(np.ones((4, 4), dtype="float32"))
    out = F.dropout(x, p=0.5, training=False, mode="downscale_in_infer")
    np.testing.assert_allclose(out.numpy(), 0.5)
    # upscale_in_train returns x untouched at inference
    out2 = F.dropout(x, p=0.5, training=False, mode="upscale_in_train")
    np.testing.assert_allclose(out2.numpy(), 1.0)


def test_reversed_rnn_respects_sequence_length():
    paddle.seed(7)
    rnn = nn.SimpleRNN(3, 4, direction="bidirect")
    T = 5
    x = paddle.to_tensor(np.random.RandomState(1).rand(2, T, 3).astype("float32"))
    lens = paddle.to_tensor(np.array([3, 5], dtype="int64"))
    out, _ = rnn(x, sequence_length=lens)
    # backward half of sample 0 at t>=3 must be zero (masked padding)
    back = out.numpy()[0, :, 4:]
    assert np.allclose(back[3:], 0.0)
    # and the valid backward outputs must equal running the same net on the
    # truncated sequence
    out_trunc, _ = rnn(x[:, :3], sequence_length=paddle.to_tensor(
        np.array([3, 3], dtype="int64")))
    np.testing.assert_allclose(back[:3], out_trunc.numpy()[0, :, 4:], atol=1e-5)


def test_conv_transpose_output_size_derives_output_padding():
    x = paddle.to_tensor(np.random.rand(1, 1, 3, 3).astype("float32"))
    w = paddle.to_tensor(np.random.rand(1, 1, 3, 3).astype("float32"))
    out = F.conv2d_transpose(x, w, stride=2, padding=0, output_size=[8, 8])
    assert out.shape == [1, 1, 8, 8]
    out7 = F.conv2d_transpose(x, w, stride=2, padding=0)
    assert out7.shape == [1, 1, 7, 7]
    # the first 7x7 block must agree (extra row/col appended at the end)
    np.testing.assert_allclose(out.numpy()[..., :7, :7], out7.numpy(), atol=1e-5)


def test_functional_update_per_group_weight_decay():
    import jax.numpy as jnp

    p1 = paddle.Parameter(np.ones(4, dtype="float32"))
    p2 = paddle.Parameter(np.ones(4, dtype="float32"))
    opt = paddle.optimizer.AdamW(learning_rate=0.1, parameters=[
        {"params": [p1], "weight_decay": 0.5},
        {"params": [p2], "weight_decay": 0.0},
    ])
    tree = {"a": p1._value, "b": p2._value}
    state = opt.functional_init(tree)
    g = {"a": jnp.zeros(4), "b": jnp.zeros(4)}
    new_p, _ = opt.functional_update(tree, g, state, 0.1)
    assert float(new_p["a"][0]) < 1.0  # decayed
    np.testing.assert_allclose(np.asarray(new_p["b"]), 1.0)  # no decay


def test_onecycle_three_phase():
    sched = paddle.optimizer.lr.OneCycleLR(
        max_learning_rate=1.0, total_steps=100, phase_pct=0.3, divide_factor=25.0,
        end_learning_rate=0.001, three_phase=True, anneal_strategy="linear")
    lrs = []
    for _ in range(101):
        lrs.append(sched())
        sched.step()
    assert abs(max(lrs) - 1.0) < 1e-6
    assert abs(lrs[30] - 1.0) < 0.04  # peak at end of phase 1
    assert abs(lrs[60] - 1.0 / 25.0) < 0.04  # back to initial_lr at end of phase 2
    assert lrs[-1] <= 0.01  # annealed to end_lr


def test_bicubic_align_corners_differs_from_bilinear():
    x = paddle.to_tensor(np.random.RandomState(2).rand(1, 1, 8, 8).astype("float32"))
    cub = F.interpolate(x, size=[15, 15], mode="bicubic", align_corners=True)
    lin = F.interpolate(x, size=[15, 15], mode="bilinear", align_corners=True)
    assert cub.shape == [1, 1, 15, 15]
    # endpoint alignment: corners must match the input exactly for both
    np.testing.assert_allclose(cub.numpy()[0, 0, 0, 0], x.numpy()[0, 0, 0, 0], atol=1e-4)
    np.testing.assert_allclose(cub.numpy()[0, 0, -1, -1], x.numpy()[0, 0, -1, -1], atol=1e-4)
    # but the interiors differ (cubic vs linear kernel)
    assert np.abs(cub.numpy() - lin.numpy()).max() > 1e-4


# ---------------------------------------------------------------- ADVICE r4
def test_geometric_trials_convention():
    """Geometric is over TRIALS k>=1 (pmf p(1-p)^(k-1), mean 1/p) — the
    reference's convention, not torch's failures-before-success (ADVICE r3)."""
    from paddle_tpu.distribution import Geometric

    import math

    g = Geometric(0.25)
    # log_prob at k=1 is log(p); at k=3 is 2*log(1-p)+log(p)
    np.testing.assert_allclose(float(g.log_prob(paddle.to_tensor(1.0))),
                               math.log(0.25), rtol=1e-6)
    np.testing.assert_allclose(float(g.log_prob(paddle.to_tensor(3.0))),
                               2 * math.log(0.75) + math.log(0.25), rtol=1e-6)
    np.testing.assert_allclose(float(g.mean), 4.0, rtol=1e-6)
    np.testing.assert_allclose(float(g.variance), 0.75 / 0.0625, rtol=1e-6)
    paddle.seed(0)
    s = g.sample([4000]).numpy()
    assert s.min() >= 1.0  # support starts at 1
    np.testing.assert_allclose(s.mean(), 4.0, rtol=0.1)


def test_inference_config_params_file_mismatch_raises():
    from paddle_tpu.inference import Config

    # matching prefixes (reference two-file spelling) are accepted
    Config("dir/model.pdmodel", "dir/model.pdiparams")
    with np.testing.assert_raises(ValueError):
        Config("dir/model.pdmodel", "elsewhere/weights.pdiparams")


def test_max_unpool_rejects_string_padding():
    x = paddle.to_tensor(np.random.RandomState(0).rand(1, 1, 8, 8).astype("float32"))
    out, idx = F.max_pool2d(x, 2, stride=2, return_mask=True)
    with np.testing.assert_raises(ValueError):
        F.max_unpool2d(out, idx, 2, stride=2, padding="SAME")


def test_fleet_init_warns_on_semantic_inert_knobs():
    import warnings

    import paddle_tpu.distributed.fleet as fleet
    from paddle_tpu.distributed import topology as topo

    prev = fleet._FLEET["strategy"]
    try:
        strategy = fleet.DistributedStrategy()
        strategy.localsgd = True
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            fleet.init(is_collective=True, strategy=strategy)
        assert any("localsgd" in str(w.message) for w in rec), \
            [str(w.message) for w in rec]
    finally:
        topo.set_hybrid_communicate_group(None)
        fleet._FLEET["strategy"] = prev


def test_eager_send_recv_raises_cross_process(monkeypatch):
    import jax

    import paddle_tpu.distributed as dist

    monkeypatch.setattr(jax, "process_count", lambda: 2)
    with np.testing.assert_raises(RuntimeError):
        dist.send(paddle.to_tensor(np.ones(2, np.float32)), dst=1)
    with np.testing.assert_raises(RuntimeError):
        dist.recv(paddle.to_tensor(np.ones(2, np.float32)), src=0)


# ---------------------------------------------------------------- r5 ADVICE


def test_quant_config_per_layer_and_kwargs():
    """QAT honors add_type_config/add_layer_config and clones quanter ctor
    args (r4 advisor medium: both were silently ignored)."""
    from paddle_tpu.quantization import (FakeQuanterWithAbsMaxObserver, QAT,
                                         QuantConfig)

    class M(nn.Layer):
        def __init__(self):
            super().__init__()
            self.a = nn.Linear(4, 4)
            self.b = nn.Linear(4, 4)

        def forward(self, x):
            return self.b(self.a(x))

    m = M()
    cfg = QuantConfig(activation=None, weight=None)
    cfg.add_type_config(nn.Linear,
                        activation=FakeQuanterWithAbsMaxObserver(bit_length=4),
                        weight=FakeQuanterWithAbsMaxObserver(bit_length=4))
    cfg.add_layer_config(m.b,
                         activation=FakeQuanterWithAbsMaxObserver(bit_length=6),
                         weight=FakeQuanterWithAbsMaxObserver(bit_length=6))
    q = QAT(cfg).quantize(m)
    assert q.a.act_quanter.bits == 4 and q.a.weight_quanter.bits == 4
    assert q.b.act_quanter.bits == 6 and q.b.weight_quanter.bits == 6
    # distinct instances per layer, not shared prototypes
    assert q.a.act_quanter is not cfg._type_configs[nn.Linear][0]


def test_ste_clip_mask_respects_bit_length():
    """4-bit STE: gradient must be zero outside scale*qmax with qmax=7,
    not the hardcoded int8 127 (r4 advisor low)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.quantization import _fake_quant

    scale = jnp.float32(1.0)
    qmax = 7.0
    g = jax.grad(lambda v: _fake_quant(v, scale, -qmax, qmax).sum())(
        jnp.asarray([3.0, 6.9, 7.1, 100.0], jnp.float32))
    np.testing.assert_allclose(np.asarray(g), [1.0, 1.0, 0.0, 0.0])


def test_dataloader_raises_on_killed_worker():
    """A SIGKILLed worker must surface as an error, not an infinite hang
    (r4 advisor low)."""
    import os
    import signal
    import time

    from paddle_tpu.io import DataLoader, Dataset

    class Slow(Dataset):
        def __len__(self):
            return 16

        def __getitem__(self, i):
            time.sleep(0.4)
            return np.full((2,), i, dtype=np.float32)

    dl = DataLoader(Slow(), batch_size=2, num_workers=2, worker_mode="process")
    it = iter(dl)
    # find the worker pids via the loader's own procs (first batch pending)
    import threading

    got, err = [], []

    def run():
        try:
            for b in it:
                got.append(b)
        except RuntimeError as e:
            err.append(e)

    t = threading.Thread(target=run)
    t.start()
    time.sleep(0.5)
    # kill every child python process of this test that looks like a worker
    import subprocess

    out = subprocess.run(["ps", "--ppid", str(os.getpid()), "-o", "pid="],
                         capture_output=True, text=True).stdout.split()
    for pid in out:
        try:
            os.kill(int(pid), signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
    t.join(timeout=30)
    assert not t.is_alive(), "DataLoader hung after worker death"
    assert err and "died" in str(err[0])


def test_asp_conv_mask_groups_reduction_tail():
    """Conv [Co,Ci,kh,kw] masks group along flattened Ci*kh*kw, keeping
    every output channel's K-groups 2:4 (r4 advisor low: grouping along Co
    broke the n:m-along-K export convention)."""
    import jax.numpy as jnp

    from paddle_tpu.incubate.asp import calculate_mask, check_sparsity

    rs = np.random.RandomState(0)
    w = jnp.asarray(rs.randn(8, 4, 3, 3).astype(np.float32))
    mask = calculate_mask(w, 2, 4)
    assert mask.shape == w.shape
    flat = np.asarray((w * mask)).reshape(8, -1)
    g = flat.reshape(8, 9, 4)  # 36 = 9 groups of 4 along Ci*kh*kw
    assert ((g != 0).sum(-1) <= 2).all()
    assert check_sparsity(w * mask, 2, 4)
    # linear [K, out] unchanged: groups along axis 0
    wl = jnp.asarray(rs.randn(8, 6).astype(np.float32))
    ml = calculate_mask(wl, 2, 4)
    gl = np.asarray((wl * ml)).T.reshape(6, 2, 4)
    assert ((gl != 0).sum(-1) <= 2).all()


# ---------------------------------------------------------------- PR-3 fixes
def test_fractional_max_pool_hand_computed_boundaries():
    """Non-self-referential oracle: in=5, out=3, u=0.5 gives boundaries
    b_i = ceil(5/3 * (i + 0.5)) -> regions [0,1), [1,3), [3,5) per axis."""
    x = paddle.to_tensor(np.arange(25, dtype="float32").reshape(1, 1, 5, 5))
    out = F.fractional_max_pool2d(x, 3, random_u=0.5)
    np.testing.assert_array_equal(
        out.numpy()[0, 0],
        [[0.0, 2.0, 4.0], [10.0, 12.0, 14.0], [20.0, 22.0, 24.0]])


def test_fractional_max_pool_seeded_determinism():
    """random_u=None draws from the framework stream (paddle.seed), not
    Python's unseeded random — same seed, same regions."""
    x = paddle.to_tensor(
        np.random.RandomState(0).rand(1, 2, 7, 7).astype("float32"))
    paddle.seed(1234)
    a = F.fractional_max_pool2d(x, 3).numpy()
    paddle.seed(1234)
    b = F.fractional_max_pool2d(x, 3).numpy()
    np.testing.assert_array_equal(a, b)
    paddle.seed(77)
    l1 = nn.FractionalMaxPool2D(4)
    paddle.seed(77)
    l2 = nn.FractionalMaxPool2D(4)
    assert l1.random_u == l2.random_u and 0.0 < l1.random_u < 1.0
    paddle.seed(77)
    l3 = nn.FractionalMaxPool3D(2)
    assert l3.random_u == l1.random_u  # same stream position


def test_poisson_entropy_static_kmax_and_trace_safety():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.distribution import Poisson

    p = Poisson(paddle.to_tensor([2.0, 5.0]))
    eager = p.entropy().numpy()
    np.testing.assert_allclose(p.entropy(kmax=80).numpy(), eager, atol=1e-5)

    with pytest.raises(ValueError, match="kmax"):
        jax.jit(lambda r: Poisson(paddle.Tensor(r)).entropy()._value)(
            jnp.asarray([2.0]))
    traced = jax.jit(
        lambda r: Poisson(paddle.Tensor(r)).entropy(kmax=80)._value)(
        jnp.asarray([2.0, 5.0]))
    np.testing.assert_allclose(np.asarray(traced), eager, atol=1e-5)


def test_adaptive_log_softmax_rejects_out_of_range_labels():
    paddle.seed(0)
    m = nn.AdaptiveLogSoftmaxWithLoss(8, 10, [4])
    x = paddle.to_tensor(np.random.RandomState(0).randn(4, 8).astype("float32"))
    out, loss = m(x, paddle.to_tensor(np.asarray([0, 3, 5, 9], "int64")))
    assert np.isfinite(float(loss))
    with pytest.raises(ValueError, match="labels must be in"):
        m(x, paddle.to_tensor(np.asarray([0, 3, 5, 10], "int64")))
    with pytest.raises(ValueError, match="labels must be in"):
        m(x, paddle.to_tensor(np.asarray([-1, 3, 5, 9], "int64")))


def test_dist_main_program_lowers_amp_scaled_step():
    """dist_main_program must include the scaler carry for AMP-scaled
    TrainSteps and re-lower the variant that produced the last batch."""
    import paddle_tpu.optimizer as opt
    from paddle_tpu.amp import GradScaler
    from paddle_tpu.distributed.auto_parallel import DistModel

    paddle.seed(0)
    m = nn.Sequential(nn.Linear(8, 16), nn.Tanh(), nn.Linear(16, 4))
    o = opt.Momentum(learning_rate=0.01, momentum=0.9,
                     parameters=m.parameters())
    step = paddle.jit.TrainStep(
        m, o, loss_fn=nn.CrossEntropyLoss(), amp_level="O1",
        amp_dtype="float16", scaler=GradScaler(init_loss_scaling=2.0**10))
    x = paddle.to_tensor(np.random.RandomState(0).randn(4, 8).astype("float32"))
    y = paddle.to_tensor(np.asarray([0, 1, 2, 3], "int64"))
    step(x, y)
    dm = DistModel.__new__(DistModel)
    dm._train_step = step
    txt = DistModel.dist_main_program(dm)
    assert isinstance(txt, str) and len(txt) > 100
    assert step._last_fn is step._compiled[next(iter(step._compiled))]


def test_fractional_max_pool_trace_safe_inside_rng_scope():
    """random_u=None must stay usable under jit: the draw comes from the
    host-side global stream, never a traced rng_scope key."""
    import jax
    from paddle_tpu.framework import random as fr

    def f(x, key):
        with fr.rng_scope(key):  # key is a TRACED value inside jit
            return F.fractional_max_pool2d(paddle.Tensor(x), 2)._value

    paddle.seed(5)
    out = jax.jit(f)(np.arange(16, dtype="float32").reshape(1, 1, 4, 4),
                     jax.random.key(1, impl="rbg"))
    assert out.shape == (1, 1, 2, 2)
