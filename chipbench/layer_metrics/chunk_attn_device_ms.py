"""Device time of one execution of the kernel the program names
``chunk_attention`` (the paged kernel behind ``ops.paged_chunk_attend``: one
layer's attention of one chunk of prompt tokens), median over the traced
window.  Found by the name stack (``trace_scopes``), so it holds whatever
the HLO instruction is called.  ``None`` where no kernel carries the name."""
import statistics

from chipbench import trace_reduce, trace_scopes

SCOPE = "chunk_attention"


def read(obs):
    events = trace_scopes.under(
        trace_scopes.clip(trace_scopes.of(obs), obs.t0, obs.t1), SCOPE)
    ms = [e[2] / 1e6 for e in events if trace_reduce.MOSAIC in e[0]]
    if not ms:
        return None
    obs.host["chunk_attn_kernels"] = len(ms)
    return statistics.median(ms)
