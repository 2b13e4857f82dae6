"""Meta-training tasks completed in the window over the window's length
(host clock; the window ends in ``block_until_ready`` on the last state)."""


def read(obs):
    return obs.get("tasks_per_s")
