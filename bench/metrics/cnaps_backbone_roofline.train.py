"""The FiLMed backbone's forward over the LITE complement as a share of
its roofline (%): the least time the chip could take for it (model FLOPs
of N - |H| images over the bf16 peak, or their lower-bound bytes over
HBM bandwidth, whichever is larger; ``bench/flops_efficientnet.py``)
over the device time of the traced steps' ops under the program's
``cnaps.class_stats/.../lite.complement``, which also holds the
complement's class sums and second moments."""
from bench import flops

KEY = "cnaps.class_stats/lite.complement"


def read(obs):
    t = obs.get("trace") or {}
    sc = t.get("scopes")
    if not sc or sc.get(KEY, 0.0) <= 0 \
            or "backbone_complement_flops_per_task" not in obs:
        return None
    tasks = t["steps"] * obs["tasks_per_step"]
    p = obs["peaks"]
    least = flops.conv_min_seconds(
        tasks * obs["backbone_complement_flops_per_task"],
        tasks * obs["backbone_complement_bytes_per_task"],
        p["bf16_flops_per_s"], p["hbm_bytes_per_s"])
    return 100.0 * least / sc[KEY]
