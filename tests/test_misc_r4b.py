"""Functional autodiff (jacobian/hessian/vjp/jvp), FusedTransformerEncoderLayer,
paddle.hub local source."""

import os

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.autograd as A
import paddle_tpu.nn as nn


def test_jacobian_and_hessian():
    x = paddle.to_tensor(np.array([1.0, 2.0, 3.0], "float32"))
    J = A.jacobian(lambda t: t * t, x)
    np.testing.assert_allclose(J.numpy(), np.diag([2.0, 4.0, 6.0]))
    H = A.hessian(lambda t: (t ** 3).sum(), x)
    np.testing.assert_allclose(H.numpy(), np.diag([6.0, 12.0, 18.0]))
    # multi-input jacobian returns a tuple
    y = paddle.to_tensor(np.array([2.0], "float32"))
    Jx, Jy = A.jacobian(lambda a, b: a * b, [x, y])
    np.testing.assert_allclose(np.diag(Jx.numpy()), [2.0, 2.0, 2.0])


def test_vjp_jvp_roundtrip():
    x = paddle.to_tensor(np.array([1.0, 2.0], "float32"))
    v = paddle.to_tensor(np.array([1.0, 0.5], "float32"))
    outs, g = A.vjp(lambda t: t * t * t, x, v)
    np.testing.assert_allclose(g.numpy(), 3 * x.numpy() ** 2 * v.numpy())
    outs, tg = A.jvp(lambda t: t * t * t, x, v)
    np.testing.assert_allclose(tg.numpy(), 3 * x.numpy() ** 2 * v.numpy())
    # default cotangent/tangent = ones
    _, g1 = A.vjp(lambda t: t.sum(), x)
    np.testing.assert_allclose(g1.numpy(), [1.0, 1.0])


def test_fused_transformer_encoder_layer_matches_unfused_shape():
    from paddle_tpu.incubate.nn import FusedTransformerEncoderLayer

    paddle.seed(0)
    layer = FusedTransformerEncoderLayer(16, 2, 32, dropout_rate=0.0)
    x = paddle.to_tensor(np.random.RandomState(0).randn(2, 5, 16).astype("float32"))
    out = layer(x)
    assert out.shape == [2, 5, 16]
    layer.eval()
    a, b = layer(x).numpy(), layer(x).numpy()
    np.testing.assert_allclose(a, b)  # deterministic in eval
    # state dict has the fused qkv parameter layout
    keys = dict(layer.state_dict()).keys()
    assert any("qkv_weight" in k for k in keys)


def test_hub_local(tmp_path):
    (tmp_path / "hubconf.py").write_text(
        "import paddle_tpu.nn as nn\n"
        "def tiny_mlp(width=8):\n"
        "    '''A tiny MLP.'''\n"
        "    return nn.Sequential(nn.Linear(4, width), nn.ReLU())\n"
        "_private = lambda: None\n")
    from paddle_tpu import hub

    assert hub.list(str(tmp_path)) == ["tiny_mlp"]
    assert "tiny MLP" in hub.help(str(tmp_path), "tiny_mlp")
    m = hub.load(str(tmp_path), "tiny_mlp", width=6)
    out = m(paddle.to_tensor(np.ones((2, 4), "float32")))
    assert out.shape == [2, 6]
    with pytest.raises(NotImplementedError):
        hub.load("owner/repo", "x", source="github")
    with pytest.raises(RuntimeError):
        hub.load(str(tmp_path), "nope")


def test_bilinear_initializer_and_global_default():
    import paddle_tpu.nn.initializer as I

    w = I.Bilinear()((2, 2, 4, 4), "float32")
    # center rows/cols carry the largest interpolation weight, corners least
    arr = np.asarray(w)
    assert arr.shape == (2, 2, 4, 4)
    assert arr[0, 0].max() == arr[0, 0, 1:3, 1:3].max()
    assert arr[0, 0, 0, 0] == arr[0, 0].min()
    with pytest.raises(ValueError):
        I.Bilinear()((4, 4), "float32")

    I.set_global_initializer(I.Constant(0.5), I.Constant(0.25))
    try:
        lin = nn.Linear(3, 3)
        assert np.allclose(lin.weight.numpy(), 0.5)
        assert np.allclose(lin.bias.numpy(), 0.25)
    finally:
        I.set_global_initializer(None, None)
    lin2 = nn.Linear(3, 3)
    assert not np.allclose(lin2.weight.numpy(), 0.5)


def test_reduce_lr_on_plateau_callback():
    import paddle_tpu.optimizer as opt
    from paddle_tpu.callbacks import ReduceLROnPlateau

    paddle.seed(0)
    net = nn.Linear(4, 2)
    o = opt.SGD(learning_rate=0.1, parameters=net.parameters())
    model = paddle.Model(net)
    model.prepare(optimizer=o, loss=nn.CrossEntropyLoss())
    cb = ReduceLROnPlateau(monitor="loss", factor=0.5, patience=2, verbose=0)
    cb.set_model(model)
    # flat losses -> after `patience` checks the lr halves
    cb.on_epoch_end(0, {"loss": 1.0})
    cb.on_epoch_end(1, {"loss": 1.0})
    cb.on_epoch_end(2, {"loss": 1.0})
    assert abs(float(o.get_lr()) - 0.05) < 1e-9
    # improvement resets the counter
    cb.on_epoch_end(3, {"loss": 0.5})
    cb.on_epoch_end(4, {"loss": 0.5})
    assert abs(float(o.get_lr()) - 0.05) < 1e-9


def test_wandb_callback_raises_without_wandb(monkeypatch):
    import sys

    from paddle_tpu.callbacks import WandbCallback

    monkeypatch.setitem(sys.modules, "wandb", None)  # force import failure
    with pytest.raises(ImportError):
        WandbCallback(project="x")


def test_fused_multi_transformer_incremental_decode_matches_full():
    """The serving-decoder oracle: feeding tokens one at a time through the
    static KV caches reproduces the full causal forward exactly."""
    from paddle_tpu.incubate.nn import FusedMultiTransformer

    paddle.seed(0)
    mt = FusedMultiTransformer(16, 2, 32, num_layers=2).eval()
    rs = np.random.RandomState(0)
    x = paddle.to_tensor(rs.randn(2, 6, 16).astype("float32"))
    full = mt(x).numpy()

    caches = mt.gen_cache(2, 8)
    outs = []
    for t in range(6):
        tok = paddle.to_tensor(x.numpy()[:, t:t + 1])
        o, caches = mt(tok, caches=caches,
                       time_step=paddle.to_tensor(np.int64(t)))
        outs.append(o.numpy())
    inc = np.concatenate(outs, axis=1)
    np.testing.assert_allclose(inc, full, atol=2e-5)
    # cache misuse raises
    with pytest.raises(ValueError):
        mt(x, caches=mt.gen_cache(2, 8))

    # post-LN (r4 weak #8: used to be refused) passes the same incremental
    # oracle, and gen_cache honors the model dtype by default
    paddle.seed(1)
    mt2 = FusedMultiTransformer(16, 2, 32, num_layers=2,
                                normalize_before=False).eval()
    full2 = mt2(x).numpy()
    caches2 = mt2.gen_cache(2, 8)
    assert caches2[0][0].numpy().dtype == np.float32  # model dtype, not hard f32
    outs2 = []
    for t in range(6):
        tok = paddle.to_tensor(x.numpy()[:, t:t + 1])
        o, caches2 = mt2(tok, caches=caches2,
                         time_step=paddle.to_tensor(np.int64(t)))
        outs2.append(o.numpy())
    np.testing.assert_allclose(np.concatenate(outs2, axis=1), full2,
                               atol=2e-5)


def test_fused_multi_transformer_paged_cache_matches_dense():
    """gen_cache(impl='paged'): the paged serving decoder reproduces the
    dense-cache incremental decode (and the full causal forward) exactly,
    with HBM bounded by pages rather than max_length."""
    from paddle_tpu.incubate.nn import FusedMultiTransformer

    paddle.seed(3)
    mt = FusedMultiTransformer(16, 2, 32, num_layers=2).eval()
    rs = np.random.RandomState(3)
    x = paddle.to_tensor(rs.randn(2, 6, 16).astype("float32"))
    full = mt(x).numpy()

    caches = mt.gen_cache(2, 8, impl="paged", page_size=4)
    assert caches[0][0] == "served"
    assert tuple(caches[0][1].shape) == (2, 2, 4, 2, 8)  # [B, PP, ps, H, D]
    # prefill 3 tokens, then decode the rest one at a time
    o, caches = mt(paddle.to_tensor(x.numpy()[:, :3]), caches=caches,
                   time_step=paddle.to_tensor(np.int64(0)))
    outs = [o.numpy()]
    for t in range(3, 6):
        tok = paddle.to_tensor(x.numpy()[:, t:t + 1])
        o, caches = mt(tok, caches=caches,
                       time_step=paddle.to_tensor(np.int64(t)))
        outs.append(o.numpy())
    inc = np.concatenate(outs, axis=1)
    np.testing.assert_allclose(inc, full, atol=2e-5)
    # misuse raises
    with pytest.raises(ValueError):
        mt(paddle.to_tensor(x.numpy()[:, :3]),
           caches=mt.gen_cache(2, 8, impl="paged"),
           time_step=paddle.to_tensor(np.int64(2)))  # prefill not at 0
    with pytest.raises(ValueError):
        mt.gen_cache(2, 8, impl="nope")
