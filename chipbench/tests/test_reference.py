"""The plain reference against the program's own forward, loss and
gradients, at a toy size on the CPU in float32."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from . import toy


@pytest.fixture(scope="module")
def family(toy_spec):
    return (toy_spec.module("models", "gpt2"),
            toy_spec.module("reference", "gpt2"))


def test_reference_imports_nothing_of_the_program():
    import os

    folder = os.path.join(toy.BENCH, "reference")
    for name in os.listdir(folder):
        if name.endswith(".py"):
            with open(os.path.join(folder, name)) as f:
                assert "paddle" not in f.read(), name


def test_weights_come_from_the_seed(family):
    _, ref = family
    a = ref.init_params(2 ** 31 + 9, toy.TOY_GPT)
    b = ref.init_params(2 ** 31 + 9, toy.TOY_GPT)
    c = ref.init_params(9, toy.TOY_GPT)
    assert set(a) == set(ref.param_shapes(toy.TOY_GPT))
    for k, shape in ref.param_shapes(toy.TOY_GPT).items():
        assert a[k].shape == shape and a[k].dtype == jnp.float32
        assert np.array_equal(a[k], b[k]) and not np.array_equal(a[k], c[k])
    assert abs(float(a["ln1_g"].mean()) - 1.0) < 0.01
    assert float(jnp.abs(a["qkv_b"]).max()) > 0          # a dropped bias shows
    bf = ref.init_params(9, toy.TOY_GPT, dtype=jnp.bfloat16)
    assert bf["wte"].dtype == jnp.bfloat16
    assert np.array_equal(bf["wte"], c["wte"].astype(jnp.bfloat16))


def test_forward_agrees_with_the_programs(family):
    import paddle_tpu as paddle

    models, ref = family
    cfg = toy.TOY_GPT
    params = ref.init_params(3, cfg)
    model = models.build(cfg, params, ref).eval()
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], (2, 40))
    out = model(input_ids=paddle.to_tensor(ids))
    got = np.asarray((out[0] if isinstance(out, (tuple, list)) else out)._value)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.logits(params, jnp.asarray(ids), cfg))
    assert got.shape == want.shape == (2, 40, cfg["vocab_size"])
    assert np.abs(got - want).max() < 2e-5 * np.abs(want).max()


def test_one_steps_loss_and_gradients_agree_leaf_by_leaf(family):
    """The program's first gradient, read from AdamW's first moment after
    one ``TrainStep``, against ``jax.grad`` of the reference's loss."""
    import paddle_tpu as paddle
    from chipbench import spec
    from chipbench.entries import train as entry

    models, ref = family
    cfg, wl = toy.TOY_GPT, dict(toy.TOY_TRAIN_MIX, **toy.TOY_TRAIN_CELL)
    params = ref.init_params(11, cfg)
    ids = np.random.default_rng(1).integers(0, cfg["vocab_size"], (4, 32))
    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.value_and_grad(ref.lm_loss)(
            params, jnp.asarray(ids), jnp.asarray(ids), cfg)
    # the step donates the weights it was given: the reference went first
    model, loss_fn, step_args = models.build_train(cfg, wl, params, ref)
    how = spec.load_module("optimizers", wl["optimizer"]["name"])
    opt = how.build(wl["optimizer"], model)
    step = paddle.jit.TrainStep(model, opt, loss_fn=loss_fn)
    loss = float(step(*step_args(paddle.to_tensor(ids)))._value)
    grads = entry._first_gradient(step, opt, model, wl["optimizer"], how)
    assert loss == pytest.approx(float(want_loss), rel=1e-6)
    assert len(grads) == 2 * 12 + 4
    for name, g in grads.items():
        key, layer = models.reference_leaf(name)
        w = want[key] if layer is None else want[key][layer]
        scale = float(jnp.abs(w).max())
        assert g.shape == w.shape, name
        assert float(jnp.abs(g - w).max()) <= 1e-4 * scale + 1e-9, name
