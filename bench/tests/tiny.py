"""A cell cut to a size the CPU runs in seconds, for the benchmark's own
tests: every width and count small, the structure of each configuration
kept."""
from __future__ import annotations

import copy

from bench import common

CPU_PEAKS = {"bf16_flops_per_s": 1e12, "int8_ops_per_s": 2e12,
             "hbm_bytes_per_s": 1e11, "hbm_bytes": 1 << 34,
             "source": "made up for CPU tests"}


def tiny_cell(config_name: str, traffic_name: str) -> common.Cell:
    config = copy.deepcopy(common.load_json(
        common.BENCH / "configs" / f"{config_name}.json"))
    traffic = copy.deepcopy(common.load_json(
        common.BENCH / "traffic" / f"{traffic_name}.json"))
    config["image_size"] = 16
    config["way"] = 3
    config["backbone"]["widths"] = [4, 8]
    config["backbone"]["feature_dim"] = 16
    config["lite"] = {"h": 3, "chunk": 4}
    traffic.update(shot=4, query_per_class=2, trace_steps=2)
    return common.Cell(name=f"{config_name}.{traffic_name}", chips=1,
                       config=config, traffic=traffic,
                       end_to_end=[], per_layer=[])
