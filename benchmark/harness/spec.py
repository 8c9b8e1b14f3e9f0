"""What `BENCHMARK.json` names, found as files by name.

A configuration is `benchmark/configs/<config>.json`, a traffic mix
`benchmark/traffic/<traffic>.json` (data; its `loop` names the code that
drives it, `benchmark/harness/loops/<loop>.py`) and a metric
`benchmark/metrics/<name>.py` (a module with `read(record) -> float |
None`).  Adding a cell, a mix, a loop or a metric adds files and entries;
no file here changes.
"""

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH_DIR)


class Spec:
    def __init__(self, path: str = os.path.join(CHECKOUT, "BENCHMARK.json"),
                 bench_dir: str = BENCH_DIR):
        with open(path) as f:
            self.doc = json.load(f)
        self.bench_dir = bench_dir
        self.run_seconds = int(self.doc["run_seconds"])

    def workload(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                with open(os.path.join(os.path.dirname(self.bench_dir),
                                       c["file"])) as f:
                    return json.load(f)
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        with open(os.path.join(self.bench_dir, "traffic",
                               f"{name}.json")) as f:
            return json.load(f)

    def metrics(self, workload: str, traced: bool) -> list:
        """The metric entries a run of `workload` reports: its end-to-end
        metrics untraced, its per-layer metrics traced."""
        key = "per_layer" if traced else "end_to_end"
        return [m for m in self.doc[key]
                if "workloads" not in m or workload in m["workloads"]]

    @staticmethod
    def loop(name: str):
        """The traffic loop a mix names: `harness/loops/<name>.py`, a
        module with a `Run(env)` class (see `cell.run_cell`)."""
        return importlib.import_module(f"{__package__}.loops.{name}")

    def reader(self, metric: str):
        path = os.path.join(self.bench_dir, "metrics", f"{metric}.py")
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + metric.replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
