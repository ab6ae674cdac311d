"""Pipeline-parallel layer containers (reference:
fleet/meta_parallel/parallel_layers/pp_layers.py — LayerDesc,
SharedLayerDesc, PipelineLayer).

TPU-native execution model: single-controller SPMD means every stage lives
in ONE program; there is no per-rank stage ownership, no send_v2/recv_v2
plumbing, no Python-driven interleaving of ranks (SURVEY.md §3.4).  Two
tiers:

- This module: the API container.  ``PipelineLayer`` keeps the reference
  construction surface (LayerDesc list, num_stages, shared embeddings) and
  executes the full stack; ``PipelineParallel.train_batch`` implements the
  reference's micro-batch semantics (split global batch, accumulate grads,
  one optimizer step) on top of the fused TrainStep.

- ``spmd_pipeline`` (pipeline_schedule.py): the performance engine — stages
  stacked on a 'pp' mesh axis inside shard_map, activations rotated with
  lax.ppermute, backward derived by AD (ppermute transposes to the reverse
  rotation, yielding the mirrored pipeline schedule the reference hand-codes
  as 1F1B).  Homogeneous transformer blocks use it via text.gpt when
  pp_degree > 1.
"""

from __future__ import annotations

import math

from ....nn.layer import Layer, Sequential
from ....tensor.tensor import Tensor


class LayerDesc:
    def __init__(self, layer_cls, *args, **kwargs):
        if not issubclass(layer_cls, Layer):
            raise TypeError("LayerDesc expects an nn.Layer subclass")
        self.layer_cls = layer_cls
        self.args = args
        self.kwargs = kwargs

    def build_layer(self):
        return self.layer_cls(*self.args, **self.kwargs)

    def __repr__(self):
        return f"LayerDesc({self.layer_cls.__name__})"


class SharedLayerDesc(LayerDesc):
    """Layer shared between stages (reference: tied embeddings in GPT);
    single-controller: the same instance is simply reused."""

    def __init__(self, key, layer_cls, forward_func=None, shared_weight_attr="weight",
                 *args, **kwargs):
        super().__init__(layer_cls, *args, **kwargs)
        self.layer_name = key
        self.forward_func = forward_func
        self.shared_weight_attr = shared_weight_attr


class PipelineLayer(Layer):
    """reference: PipelineLayer(layers=[...], num_stages=pp, topology=hcg).

    Builds every LayerDesc, records the stage partition (used by the spmd
    engine and by shard-aware checkpointing), and runs the whole stack.
    """

    def __init__(self, layers, num_stages=None, topology=None, loss_fn=None,
                 seg_method="uniform", recompute_interval=0, **kwargs):
        super().__init__()
        self._loss_fn = loss_fn
        self._topo = topology
        if num_stages is None and topology is not None:
            num_stages = topology.get_pipe_parallel_world_size() \
                if hasattr(topology, "get_pipe_parallel_world_size") else 1
        self._num_stages = num_stages or 1
        self._recompute_interval = recompute_interval

        self._shared = {}
        built = []
        for desc in layers:
            if isinstance(desc, SharedLayerDesc):
                if desc.layer_name in self._shared:
                    layer = self._shared[desc.layer_name]
                else:
                    layer = desc.build_layer()
                    self._shared[desc.layer_name] = layer
                built.append((layer, desc.forward_func))
            elif isinstance(desc, LayerDesc):
                built.append((desc.build_layer(), None))
            elif isinstance(desc, Layer):
                built.append((desc, None))
            elif callable(desc):
                built.append((desc, None))
            else:
                raise TypeError(f"bad pipeline entry {desc!r}")
        self.run_function = []
        for i, (layer, ffn) in enumerate(built):
            if isinstance(layer, Layer):
                self.add_sublayer(str(i), layer)
            self.run_function.append((layer, ffn))

        n = len(self.run_function)
        per = int(math.ceil(n / self._num_stages))
        self.segment_parts = [min(i * per, n) for i in range(self._num_stages + 1)]
        self.segment_parts[-1] = n

    def get_stage_from_index(self, idx):
        for s in range(self._num_stages):
            if self.segment_parts[s] <= idx < self.segment_parts[s + 1]:
                return s
        return self._num_stages - 1

    def stage_layers(self, stage):
        lo, hi = self.segment_parts[stage], self.segment_parts[stage + 1]
        return [l for l, _ in self.run_function[lo:hi]]

    def forward(self, x):
        if self._recompute_interval:
            from ..utils.recompute import recompute as _recompute

            i, fns = 0, self.run_function
            while i < len(fns):
                j = min(i + self._recompute_interval, len(fns))
                def run_span(h, _fns=fns[i:j]):
                    for layer, ffn in _fns:
                        h = ffn(layer, h) if ffn is not None else layer(h)
                    return h
                x = _recompute(run_span, x)
                i = j
            return x
        for layer, ffn in self.run_function:
            x = ffn(layer, x) if ffn is not None else layer(x)
        return x


class PipelineParallel(Layer):
    """reference: fleet/meta_parallel/pipeline_parallel.py — the runtime that
    owns the micro-batch schedule.  train_batch(data, optimizer[, scaler])
    splits the global batch into ``accumulate_steps`` micro-batches,
    accumulates grads in one fused program each, and steps once."""

    def __init__(self, layers, hcg=None, strategy=None):
        super().__init__()
        self._layers = layers
        self._hcg = hcg
        self._strategy = strategy
        cfg = (strategy.pipeline_configs if strategy is not None else {}) or {}
        self._micro_batches = int(cfg.get("accumulate_steps", 1))
        self._train_step = None

    def forward(self, *args, **kwargs):
        return self._layers(*args, **kwargs)

    def parameters(self, include_sublayers=True):
        return self._layers.parameters(include_sublayers)

    def named_parameters(self, prefix="", include_sublayers=True):
        return self._layers.named_parameters(prefix, include_sublayers)

    def state_dict(self, *a, **k):
        return self._layers.state_dict(*a, **k)

    def set_state_dict(self, *a, **k):
        return self._layers.set_state_dict(*a, **k)

    def train_batch(self, data, optimizer, lr_scheduler=None, scaler=None,
                    loss_fn=None):
        from ....jit.train_step import TrainStep

        x, y = data
        loss_fn = loss_fn or self._layers._loss_fn or (lambda out, lbl: out.mean())
        m = self._micro_batches
        bsz = x.shape[0]
        if bsz % m:
            raise ValueError(f"batch {bsz} not divisible by accumulate_steps {m}")
        if self._train_step is None or self._train_step.optimizer is not optimizer:
            # one fused program: grads accumulated over the m micro-batches
            # inside the step (lax.scan), ONE optimizer update per call —
            # the reference's gradient-merge semantics.
            self._train_step = TrainStep(self._layers, optimizer,
                                         loss_fn=loss_fn, accumulate_steps=m)
        loss = self._train_step(x, y)
        if lr_scheduler is not None:
            lr_scheduler.step()
        return Tensor(loss._value if isinstance(loss, Tensor) else loss)
