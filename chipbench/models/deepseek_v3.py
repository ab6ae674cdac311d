"""How the program builds the ``deepseek_v3`` family, and how the benchmark's
seeded weights and batches get into it.  The only file of the family that
imports the program; the reference module is handed in, never imported from
here.

The reference's weights come from the host (``reference/deepseek_v3.py``
says why) and every norm is taken leaf by leaf, so that beside the step's
own state the device holds one leaf of the comparison at a time.

**The expert layers' counts.**  The layers count on the device; a training
loop reads the counts into the program's ``moe.*`` series with
``step.sync()``.  The train entry syncs once, after the first step, and the
only calls of the family around the window are ``change_norms`` (the
compared steps are over) and ``tokens_per_sample`` (the window has closed,
the profiler has not started).  So both read the counts (``_publish``), and
``window_counters()`` is what the series gained between the two: the
window's steps and the cell's ``extra_warm_steps`` before it.  Nothing is
read inside a step, and calling either function more often changes nothing.
"""

from __future__ import annotations

import json
import sys
import weakref

import numpy as np

from chipbench import program_counters
from chipbench.models.gpt2 import TokenRows

_LAYER = {"input_layernorm.weight": "ln1",
          "self_attn.q_proj.weight": "q_w",
          "self_attn.kv_a_proj_with_mqa.weight": "kva_w",
          "self_attn.kv_a_layernorm.weight": "kv_norm",
          "self_attn.kv_b_proj.weight": "kvb_w",
          "self_attn.o_proj.weight": "o_w",
          "post_attention_layernorm.weight": "ln2"}
_DENSE = {"mlp.gate_proj.weight": "ffn_gate", "mlp.up_proj.weight": "ffn_up",
          "mlp.down_proj.weight": "ffn_down"}
_EXPERT = {"mlp.gate_weight": "router_w", "mlp.w_gate": "exp_gate",
           "mlp.w_up": "exp_up", "mlp.w_down": "exp_down",
           "mlp.shared_gate": "sh_gate", "mlp.shared_up": "sh_up",
           "mlp.shared_down": "sh_down"}
_TOP = {"model.embed_tokens.weight": "embed", "lm_head.weight": "head",
        "model.norm.weight": "norm_f"}

# module docstring, "The expert layers' counts"
_model = None           # weakref: the harness hands tokens_per_sample no model
_counted_from = None    # the moe.* series when the compared steps were over
_counted = {}


def reference_leaf(program_name):
    """``(reference key, decoder layer or None)`` of a program parameter."""
    if program_name in _TOP:
        return _TOP[program_name], None
    parts = program_name.split(".", 3)
    if len(parts) == 4 and parts[:2] == ["model", "layers"]:
        for names in (_LAYER, _DENSE, _EXPERT):
            if parts[3] in names:
                return names[parts[3]], int(parts[2])
    raise KeyError(f"no reference leaf for {program_name!r}")


def _leaf(params, name):
    """The reference's leaf of a program parameter: the expert leaves are
    stacked over the layers after the leading dense ones."""
    key, layer = reference_leaf(name)
    if layer is None:
        return params[key]
    if key in _EXPERT.values():
        layer -= len(params["ffn_gate"])
    return params[key][layer]


def build(cfg, params, ref, recompute=False):
    """``DeepseekV3ForCausalLM`` at the configuration's sizes and share,
    holding ``params`` (the reference's stacked dict) under the program's
    own names."""
    import jax.numpy as jnp

    from paddle_tpu.tensor.tensor import Tensor
    from paddle_tpu.text.models import DeepseekV3ForCausalLM

    z = ref.sizes(cfg)
    model = DeepseekV3ForCausalLM(
        recompute=recompute, vocab_size=z["V"], hidden_size=z["H"],
        intermediate_size=z["I"], moe_intermediate_size=z["F"],
        num_hidden_layers=z["L"], num_attention_heads=z["nh"],
        kv_lora_rank=z["rank"], q_lora_rank=cfg.get("q_lora_rank"),
        qk_nope_head_dim=z["nope"], qk_rope_head_dim=z["rope"],
        v_head_dim=z["vd"], n_routed_experts=z["E"],
        experts_held=z["held"], expert_offset=z["offset"],
        n_shared_experts=z["shared"], num_experts_per_tok=z["k"],
        first_k_dense_replace=z["Ld"], norm_topk_prob=z["norm_topk"],
        routed_scaling_factor=z["scale"], rms_norm_eps=z["eps"],
        rope_theta=z["theta"], rope_interleave=z["interleave"],
        initializer_range=float(cfg.get("initializer_range", 0.02)),
        bias_update_speed=float(cfg.get("bias_update_speed", 0.0)))
    state = {name: Tensor(jnp.asarray(_leaf(params, name)))
             for name, _ in model.named_parameters()}
    missing, unexpected = model.set_state_dict(state)
    missing = [m for m in missing if m not in dict(model.named_buffers())]
    if missing or unexpected:
        raise RuntimeError(f"weights do not fit the model: missing "
                           f"{missing}, unexpected {unexpected}")
    return model


# ----------------------------------------------------------------- training
def make_dataset(seed, cfg, wl):
    """Packed random token ids from the configuration's slice of the
    vocabulary."""
    return TokenRows(seed, int(wl["dataset_samples"]), int(wl["seq_len"]),
                     int(cfg["vocab_size"]))


def build_train(cfg, wl, params, ref):
    """``(model, loss_fn, step_args)``: the model returns its own loss when
    given ``labels``, so there is no separate loss function."""
    global _model, _counted_from
    model = build(cfg, params, ref, recompute=bool(wl.get("recompute")))
    model.train()
    _model, _counted_from = weakref.ref(model), None
    _counted.clear()

    def step_args(batch):
        return ({"input_ids": batch, "labels": batch},)

    return model, None, step_args


def reference_batch(batch):
    ids = np.asarray(batch._value)
    return ids, ids


def _publish():
    """The expert layers' device counts into the registry; the ``moe.*``
    series as they stand after it."""
    model = _model() if _model is not None else None
    for layer in model.sublayers() if model is not None else ():
        if hasattr(layer, "publish_load"):
            layer.publish_load()
    return program_counters.snapshot(("moe.",))


def window_counters():
    """What the ``moe.*`` series gained from ``change_norms`` to the last
    ``tokens_per_sample``; empty before both have run."""
    return dict(_counted)


def tokens_per_sample(cfg, wl):
    if _counted_from is not None:
        _counted.update(program_counters.delta(_counted_from, _publish()))
        print(f"[chipbench] experts' window counts {json.dumps(_counted)}",
              file=sys.stderr, flush=True)
    return int(wl["seq_len"])


# ------------------------------------------------------- norms for `correct`
def _norms(named_values, ref, cfg, minus=None):
    """Per-leaf norms of program arrays (less ``minus``, leaf by leaf) in the
    reference's layout ``{key: [per layer]}``."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32

    @jax.jit
    def norm(value, less):
        return jnp.sqrt(jnp.sum(jnp.square(value.astype(f32) - less)))

    out = {}
    for name in sorted(named_values):
        key, layer = reference_leaf(name)
        less = f32(0.0) if minus is None else minus[name]
        out.setdefault(key, {})[layer or 0] = float(
            norm(named_values[name], less))
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
    """Per-leaf norm of (parameter now - parameter as drawn from the seed).
    The compared steps are over: the window's counting starts here."""
    global _counted_from
    _counted_from = _publish()
    p0 = ref.init_params(seed, cfg)
    now = {name: p._value for name, p in model.named_parameters()}
    return _norms(now, ref, cfg,
                  minus={name: _leaf(p0, name) for name in now})
