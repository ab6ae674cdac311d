"""Performance attribution + SLO walkthrough (paddle_tpu.observability).

Runs on the CPU backend: serves a small mixed workload through the
continuous-batching engine under an SLO policy, trains a few fused steps,
then prints the per-program roofline attribution report (which compiled
program spent the device time, and whether it is HBM- or compute-bound
against the configured ceilings), the SLO attainment/goodput summary, and
the live /statusz program table.

    JAX_PLATFORMS=cpu python examples/observability_perf.py
"""

import json
import os
import urllib.request

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
# roofline ceilings: on a real chip these come from the datasheet tables
# by device kind; the CPU test mesh has no entry there, so configure the
# v5e datasheet lines explicitly
os.environ.setdefault("PADDLE_PEAK_FLOPS", "197e12")
os.environ.setdefault("PADDLE_HBM_GBS", "819")

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.optimizer as opt
from paddle_tpu.observability import perf
from paddle_tpu.serving import ServingEngine, SLOPolicy
from paddle_tpu.text.models.gpt import GPTForCausalLM

# ------------------------------------------------------- serve under SLO
paddle.seed(0)
model = GPTForCausalLM(vocab_size=128, hidden_size=64, num_hidden_layers=2,
                       num_attention_heads=4,
                       max_position_embeddings=128).eval()

policy = SLOPolicy(ttft_s=30.0, itl_s=5.0, e2e_s=120.0, objective=0.95)
engine = ServingEngine(model, num_slots=2, page_size=16, max_model_len=96,
                       slo=policy, telemetry_port=0)
rs = np.random.RandomState(0)
with engine:
    handles = [
        engine.submit(rs.randint(1, 120, (8,)), max_new_tokens=12),
        engine.submit(rs.randint(1, 120, (8,)), max_new_tokens=8,
                      temperature=0.8),
        engine.submit(rs.randint(1, 120, (24,)), max_new_tokens=10),
    ]
    for h in handles:
        h.result(timeout=600)

    print("SLO summary (per replica):")
    print(json.dumps(engine.slo_accountant.summary(), indent=2))

    from paddle_tpu.observability import telemetry

    url = telemetry.get_server().url
    statusz = json.load(urllib.request.urlopen(f"{url}/statusz", timeout=10))
    table = statusz["perf_programs"]
    print(f"\n/statusz perf_programs (ridge "
          f"{table['ridge_flop_per_byte']:.0f} FLOP/byte):")
    for row in table["programs"]:
        print(f"  {row['program']:<16} calls={row['calls']:<5} "
              f"dev_s={row['device_seconds']:.4f} regime={row['regime']}")

# ------------------------------------------------ a few fused train steps
m = GPTForCausalLM(vocab_size=128, hidden_size=64, num_hidden_layers=2,
                   num_attention_heads=4, max_position_embeddings=128)
o = opt.AdamW(learning_rate=1e-3, parameters=m.parameters())
step = paddle.jit.TrainStep(m, o, loss_fn=None)
ids = paddle.to_tensor(rs.randint(1, 120, (4, 32)).astype("int64"))
for _ in range(4):
    step({"input_ids": ids, "labels": ids})

# ------------------------------------------------- the attribution report
# resolve=True runs the pending XLA cost_analysis thunks (a re-lower +
# compile per program family — exactly what a telemetry scrape is NOT
# allowed to do; set PADDLE_PERF_COST=1 to let /statusz scrapes kick the
# resolution on a background thread instead)
print("\n" + perf.report(top=3, resolve=True))
