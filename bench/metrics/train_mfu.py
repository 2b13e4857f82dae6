"""Model FLOPs of one task (from shapes, ``bench/flops.py``) times tasks
per second in the window, as a share of the chip's bf16 peak (%)."""


def read(obs):
    if "tasks_per_s" not in obs:
        return None
    return (100.0 * obs["model_flops_per_task"] * obs["tasks_per_s"]
            / obs["peaks"]["bf16_flops_per_s"])
