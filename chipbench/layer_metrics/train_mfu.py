"""The whole training step's share of the chip's peak: analytic forward +
backward operations per sample (``costs/<family>.py``, nothing recomputed
counted) times the window's samples per second, over chips x the bf16
peak."""
from chipbench import peaks


def read(obs):
    ips = obs.host.get("train_ips")
    if not ips or obs.peak is None:
        return None
    costs = obs.spec.module("costs", obs.config["family"])
    per_sample = costs.train_flops_per_sample(
        obs.config, obs.host["tokens_per_sample"])
    least = per_sample / (obs.cell.chips * obs.peak["bf16_flops"])
    return peaks.share_percent(least, 1.0 / ips, "train_mfu")
