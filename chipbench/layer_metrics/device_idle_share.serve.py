"""Share of the traced window in which no operation ran on the device."""


def read(obs):
    d = obs.device
    return 100.0 * (1.0 - d["busy_s"] / d["window_s"])
