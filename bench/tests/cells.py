"""Small cells for the CPU tests: the real configurations' keys at sizes
the Pallas interpreter runs in seconds."""
import copy
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
CPU_PEAKS = {"bf16_flops_per_s": 1.0e12, "hbm_bytes_per_s": 1.0e11}


def load(path):
    return json.loads((BENCH / path).read_text())


#: per traffic: (points, clusters, mixture components) at which a sound
#: run agrees with the reference within the cell's limits.  Whole-data
#: Lloyd keeps one component a centroid: over 25-50 steps among dense near
#: ties its trajectory and the reference's part ways at small sizes.
SHAPES = {"ipkmeans": (8192, 64, 4), "train": (16384, 64, 64)}
#: many centroids to a component: the near ties where the control's
#: rounding shows, as it does at full size
DENSE = (8192, 64, 4)


def small(name: str, traffic: str, shape=None) -> dict:
    """The named cell of BENCHMARK.json, shrunk to ``shape`` (default its
    ``SHAPES`` entry) at the published 128 dimensions, with 16 subsets."""
    spec = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    cell = next(w for w in spec["workloads"] if w["name"] == name)
    config = copy.deepcopy(load(f"configs/{cell['config']}.json"))
    n, k, comps = shape or SHAPES[traffic]
    config.update(n_points=n, clusters=k)
    config["data"]["components"] = comps
    if "ipkmeans" in config:
        config["ipkmeans"]["num_subsets"] = 16
    traffic_file = load(f"traffic/{traffic}.json")

    def here(m):
        return name in m.get("workloads", [name])

    end_to_end = [m for m in spec["end_to_end"] if here(m)]
    return {"cell": cell, "config": config, "traffic": traffic_file,
            "limits": load(f"limits/{name}.json"), "end_to_end": end_to_end,
            "per_layer": [m for m in spec["per_layer"] if here(m)]}
