"""Warm restarts: the compile ledger + warmup manifests
(README "Program lifecycle & warmup").

A serving process's first request per program-store key pays the full
trace + XLA compile stall — tens of seconds for real models.  This demo
runs the SAME tiny GPT through a cold restart and a warm restart:

- cold: a fresh engine serves one request; its TTFT decomposition
  (``RequestHandle.ttft_breakdown()``) shows where the time went
  (``queue_s / compile_s / prefill_s``), the process-wide
  :class:`~paddle_tpu.observability.programs.ProgramLedger` shows every
  minted program with its compile wall and the trace id that paid it,
  and ``engine.capture_manifest()`` saves the store's key set;
- warm: a second engine over a fresh same-seed model replays the
  manifest with ``engine.warmup(path)`` BEFORE admission, so its first
  real request dispatches with ZERO new traces, ``compile_s == 0`` and
  byte-identical greedy output.

In production the manifest is captured once from a long-lived replica
and replayed on every restart / scale-up
(``ReplicaPool(model, warmup="manifest.json", ...)``), turning the
cold-start TTFT cliff into a deploy-time cost.

Run (CPU-friendly; compiles are ~1s here, minutes on real models):

    JAX_PLATFORMS=cpu python examples/serve_gpt_warm.py
"""

import json
import os
import tempfile

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.observability import programs
from paddle_tpu.serving import ServingEngine
from paddle_tpu.text.models import GPTForCausalLM

PAGE = 16
S0, MAX_NEW = 32, 48


def build_model():
    paddle.seed(0)
    return GPTForCausalLM(vocab_size=128, hidden_size=128,
                          num_hidden_layers=4, num_attention_heads=4,
                          max_position_embeddings=256).eval()


def serve_one(engine, prompt):
    with engine:
        h = engine.submit(prompt, max_new_tokens=MAX_NEW)
        ids = list(h.result(timeout=600))
    return ids, h.ttft_breakdown()


def main():
    prompt = np.random.RandomState(0).randint(1, 128, (S0,)).tolist()
    manifest_path = os.path.join(tempfile.gettempdir(),
                                 "gpt_warm_manifest.json")

    # ---------------------------------------------------- cold restart
    print("=== cold restart: first request pays the compiles ===")
    model = build_model()
    engine = ServingEngine(model, num_slots=4, page_size=PAGE,
                           max_model_len=S0 + MAX_NEW)
    cold_ids, cold_bd = serve_one(engine, prompt)
    print(f"TTFT {cold_bd['ttft_s']:.3f}s = queue {cold_bd['queue_s']:.4f}s"
          f" + compile {cold_bd['compile_s']:.3f}s"
          f" + prefill {cold_bd['prefill_s']:.4f}s"
          f"  (cold={cold_bd['cold']})")

    led = programs.ledger()
    # the rows carry the build's own seconds as JAX reported them; the
    # executable's size, flops and bytes resolve on demand (a second build)
    led.resolve_analysis()
    print("\nprogram ledger (the /statusz 'programs' table):")
    for row in led.rows():
        print(f"  {row['family']:<22} {row['cold']:<5}"
              f" wall {row['compile_s'] or 0:.3f}s ="
              f" trace {row.get('trace_s', 0):.3f}"
              f" + lower {row.get('lower_s', 0):.3f}"
              f" + xla {row.get('backend_compile_s', 0):.3f}"
              f" + cache {row.get('cache_load_s', 0):.3f} + waits;"
              f" {row.get('executable_bytes')} B"
              f" paid-by {str(row['trace_id'])[:8]}")

    engine.capture_manifest().save(manifest_path)
    n_keys = len(json.load(open(manifest_path))["keys"])
    print(f"\ncaptured {n_keys}-key manifest -> {manifest_path}")

    # ---------------------------------------------------- warm restart
    print("\n=== warm restart: manifest replayed before admission ===")
    model2 = build_model()  # a fresh process would rebuild from checkpoint
    engine2 = ServingEngine(model2, num_slots=4, page_size=PAGE,
                            max_model_len=S0 + MAX_NEW)
    info = engine2.warmup(manifest_path)
    print(f"warmup replayed {info['warmed']} programs"
          f" in {info['seconds']:.2f}s (skipped {info['skipped']})")

    traces0 = engine2.program_traces()
    warm_ids, warm_bd = serve_one(engine2, prompt)
    warm_traces = engine2.program_traces() - traces0

    print(f"TTFT {warm_bd['ttft_s']:.4f}s, compile"
          f" {warm_bd['compile_s']:.1f}s, new traces {warm_traces}")
    print(f"\ncold TTFT {cold_bd['ttft_s']:.3f}s ->"
          f" warm TTFT {warm_bd['ttft_s']:.4f}s"
          f" ({cold_bd['ttft_s'] / max(warm_bd['ttft_s'], 1e-9):.0f}x)")
    assert warm_traces == 0, "warmed engine must not trace"
    assert warm_ids == cold_ids, "greedy output must be byte-identical"
    print("OK: zero traces after warmup, byte-identical greedy output")


if __name__ == "__main__":
    main()
