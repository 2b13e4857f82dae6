"""Share of the traced training window in which no operation ran on the
device (%): 1 - union of device-op intervals / window."""


def read(obs):
    t = obs.get("trace")
    if not t or "tasks_per_s" not in obs:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
