"""The whole serving step's share of the chip's peak over the measured
window: the operations that every prompt and output token of the window
requires (``costs/gpt2.py``: matrix products at 2 per weight per token,
attention by each token's real context), over window seconds x chips x the
bf16 peak.  A prompt counts in the window its first token falls in."""
from chipbench import peaks


def read(obs):
    flops = obs.host.get("flops_in_window")
    if not flops or obs.peak is None:
        return None
    least = flops / (obs.cell.chips * obs.peak["bf16_flops"])
    return peaks.share_percent(least, obs.host["window_s"], "serve_mfu")
