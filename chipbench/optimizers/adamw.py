"""How the program builds ``adamw`` (a cell's ``optimizer.name``), and how
the first gradient is read back from its state.  The train entry finds this
file by that name: another optimizer is another file beside it."""

from __future__ import annotations


def build(h, model):
    import paddle_tpu.optimizer as opt

    return opt.AdamW(learning_rate=h["learning_rate"], beta1=h["beta1"],
                     beta2=h["beta2"], epsilon=h["epsilon"],
                     weight_decay=h["weight_decay"],
                     parameters=model.parameters())


def first_gradient(state, h):
    """The gradient of the first step as the optimizer got it, from one
    parameter's state after that step: the first moment is ``(1-beta1) g``."""
    return state["m"]._value / (1.0 - h["beta1"])
