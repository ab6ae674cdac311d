"""Share of the window the training loop spent blocked in ``next(loader)``,
by the benchmark's host clock."""


def read(obs):
    wait, window = obs.host.get("input_wait_s"), obs.host.get("window_s")
    if wait is None or not window:
        return None
    return 100.0 * wait / window
