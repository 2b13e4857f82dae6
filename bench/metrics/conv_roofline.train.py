"""The traced training steps' convolutions as a share of their roofline
(%): the least time the chip could take for the necessary convolution
work (operations over peak FLOP/s or lower-bound bytes over peak
bandwidth, whichever is larger; ``bench/flops.py``) over the device time
of operations whose XLA category is convolution."""
from bench import flops


def read(obs):
    t = obs.get("trace")
    if not t or t["conv_s"] <= 0 or "conv_flops_per_task" not in obs:
        return None
    tasks = t["steps"] * obs["tasks_per_step"]
    p = obs["peaks"]
    least = flops.conv_min_seconds(tasks * obs["conv_flops_per_task"],
                                   tasks * obs["conv_bytes_per_task"],
                                   p["bf16_flops_per_s"], p["hbm_bytes_per_s"])
    return 100.0 * least / t["conv_s"]
