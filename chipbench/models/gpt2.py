"""How the program builds the ``gpt2`` family, and how the benchmark's seeded
weights and batches get into it.  The only file of the family that imports
the program; the reference module is handed in, never imported from here.
"""

from __future__ import annotations

import numpy as np

#: program parameter name inside a decoder layer -> reference key
_LAYER = {"ln1.weight": "ln1_g", "ln1.bias": "ln1_b",
          "qkv.weight": "qkv_w", "qkv.bias": "qkv_b",
          "out_proj.weight": "proj_w", "out_proj.bias": "proj_b",
          "ln2.weight": "ln2_g", "ln2.bias": "ln2_b",
          "ffn1.weight": "fc_w", "ffn1.bias": "fc_b",
          "ffn2.weight": "fc2_w", "ffn2.bias": "fc2_b"}
_TOP = {"gpt.word_embeddings.weight": "wte",
        "gpt.position_embeddings.weight": "wpe",
        "gpt.final_ln.weight": "lnf_g", "gpt.final_ln.bias": "lnf_b"}


def reference_leaf(program_name):
    """``(reference key, layer index or None)`` of a program parameter."""
    if program_name in _TOP:
        return _TOP[program_name], None
    parts = program_name.split(".", 3)
    if len(parts) != 4 or parts[:2] != ["gpt", "layers"] \
            or parts[3] not in _LAYER:
        raise KeyError(f"no reference leaf for {program_name!r}")
    return _LAYER[parts[3]], int(parts[2])


def _leaf(params, name):
    key, layer = reference_leaf(name)
    return params[key] if layer is None else params[key][layer]


def build(cfg, params, ref, dtype=None):
    """``GPTForCausalLM`` at the configuration's sizes, holding ``params``
    (the reference's stacked dict) under the program's own names."""
    from paddle_tpu.tensor.tensor import Tensor
    from paddle_tpu.text.models import GPTForCausalLM

    z = ref.sizes(cfg)
    model = GPTForCausalLM(
        vocab_size=z["V"], hidden_size=z["H"], num_hidden_layers=z["L"],
        num_attention_heads=z["nh"], intermediate_size=z["I"],
        max_position_embeddings=z["P"], hidden_act="gelu",
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
    if dtype is not None:
        model = model.astype(dtype)
    state = {name: Tensor(_leaf(params, name))
             for name, _ in model.named_parameters()}
    missing, unexpected = model.set_state_dict(state)
    if missing or unexpected:
        raise RuntimeError(f"weights do not fit the model: missing "
                           f"{missing}, unexpected {unexpected}")
    return model


# ----------------------------------------------------------------- training
class TokenRows:
    """Packed random token sequences from the seed, in memory; pure numpy,
    so a loader's workers never touch JAX.  Every row differs."""

    def __init__(self, seed, n, seq, vocab):
        rng = np.random.default_rng(int(seed) % (1 << 62))
        self.rows = rng.integers(0, vocab, (n, seq), dtype=np.int64)

    def __len__(self):
        return len(self.rows)

    def __getitem__(self, i):
        return self.rows[i]


def make_dataset(seed, cfg, wl):
    return TokenRows(seed, int(wl["dataset_samples"]), int(wl["seq_len"]),
                     int(cfg["vocab_size"]))


def build_train(cfg, wl, params, ref):
    """``(model, loss_fn, step_args)``: the model returns its own loss when
    given ``labels``, so there is no separate loss function."""
    model = build(cfg, params, ref)
    model.train()

    def step_args(batch):
        return ({"input_ids": batch, "labels": batch},)

    return model, None, step_args


def reference_batch(batch):
    ids = np.asarray(batch._value)
    return ids, ids


def tokens_per_sample(cfg, wl):
    return int(wl["seq_len"])


# ------------------------------------------------------- norms for `correct`
def _norms(named_values, ref, cfg, minus=None):
    """Per-leaf norms of program arrays (less ``minus``, leaf by leaf) in the
    reference's layout ``{key: [per layer]}``, the fused QKV bias as its
    three parts (``ref.split_qkv_bias``), in one jitted call."""
    import jax
    import jax.numpy as jnp

    names = sorted(named_values)
    f32 = jnp.float32

    @jax.jit
    def norms(vals, less):
        if less is not None:
            vals = [v.astype(f32) - l.astype(f32) for v, l in zip(vals, less)]
        out = []
        for name, v in zip(names, vals):
            v = v.astype(f32)
            if reference_leaf(name)[0] == "qkv_b":
                v = ref.split_qkv_bias(v, cfg)
                out.append(jnp.sqrt(jnp.sum(jnp.square(v), -1)))
            else:
                out.append(jnp.sqrt(jnp.sum(jnp.square(v))))
        return out

    less = None if minus is None else [minus[n] for n in names]
    got = norms([named_values[n] for n in names], less)
    out = {}
    for name, value in zip(names, got):
        key, layer = reference_leaf(name)
        layer = 0 if layer is None else layer
        if key == "qkv_b":
            for j, part in enumerate("qkv"):
                out.setdefault(f"{key}.{part}", {})[layer] = float(value[j])
        else:
            out.setdefault(key, {})[layer] = float(value)
    return {k: [v[i] for i in sorted(v)] for k, v in out.items()}


def gradient_tree(named_gradients):
    """Program gradients by name as the reference's tree of stacked leaves,
    on the host (the device is the window's)."""
    layers, out = {}, {}
    for name, value in named_gradients.items():
        key, layer = reference_leaf(name)
        if layer is None:
            out[key] = np.asarray(value, np.float32)
        else:
            layers.setdefault(key, {})[layer] = np.asarray(value, np.float32)
    for key, by_layer in layers.items():
        out[key] = np.stack([by_layer[i] for i in sorted(by_layer)])
    return out


def gradient_norms(named_gradients, ref, cfg):
    return _norms(named_gradients, ref, cfg)


def change_norms(model, ref, seed, cfg):
    """Per-leaf norm of (parameter now - parameter as drawn from the seed)."""
    p0 = ref.init_params(seed, cfg)
    now = {name: p._value for name, p in model.named_parameters()}
    return _norms(now, ref, cfg,
                  minus={name: _leaf(p0, name) for name in now})
