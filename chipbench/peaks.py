"""The one table of peaks, keyed by ``device_kind`` exactly as JAX reports it.
A device that is not in the table is an error, never a default, and nothing
in the environment overrides a number."""

from __future__ import annotations

import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind):
    with open(_PATH) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{_PATH}; known: {sorted(table)}")
    return dict(table[device_kind])


def roofline_seconds(flops, bytes_moved, peak):
    """The least time the chip could take, and which bound gives it."""
    t_flops = flops / peak["bf16_flops"]
    t_bytes = bytes_moved / peak["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "bandwidth")


def share_percent(least_seconds, measured_seconds, what):
    """A share of a roofline or of a peak, in percent.  One over 100% means
    the operations or bytes were counted too high or the time leaves out part
    of the work: that fails the run rather than printing."""
    if not measured_seconds or measured_seconds <= 0:
        return None
    share = 100.0 * least_seconds / measured_seconds
    if share > 100.0:
        raise ValueError(f"{what}: {share:.2f}% of its peak is over 100%: "
                         "the cost function or the measured time is wrong")
    return share
