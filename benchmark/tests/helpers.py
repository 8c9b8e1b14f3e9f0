"""A benchmark tree of its own for the CPU tests: the real metric readers
and traffic mixes, plus tiny configurations and cells added as files."""

import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny_tree(tmp, rounds: int = 256, chunk: int = 64):
    """-> Spec over a copy of the benchmark whose cells are every real
    traffic mix on every real configuration, on tiny stores (chunk
    `chunk`, verify pad 64): `tiny_<config>.<mix>`."""
    from harness.spec import Spec
    root = os.path.join(str(tmp), "checkout")
    bench = os.path.join(root, "benchmark")
    for sub in ("metrics", "traffic"):
        shutil.copytree(os.path.join(BENCH, sub), os.path.join(bench, sub))
    os.makedirs(os.path.join(bench, "configs"))
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        doc = json.load(f)
    configs, cells = [], []
    for c in doc["configs"]:
        with open(os.path.join(os.path.dirname(BENCH), c["file"])) as f:
            body = json.load(f)
        body["rounds"] = rounds
        body["config_overrides"] = {"sync_chunk": chunk, "verify_pad": 64}
        name = "tiny_" + c["name"]
        path = f"benchmark/configs/{name}.json"
        with open(os.path.join(root, path), "w") as f:
            json.dump(body, f)
        configs.append(dict(c, name=name, file=path))
    mixes = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic"))
                   if f.endswith(".json"))
    for c in configs:
        for mix in mixes:
            cells.append({"name": f"{c['name']}.{mix}", "config": c["name"],
                          "traffic": mix, "chips": 1, "why": "CPU test"})
    doc["configs"], doc["workloads"] = configs, cells
    for m in doc["end_to_end"] + doc["per_layer"]:
        m.pop("workloads", None)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    for name in mixes:
        p = os.path.join(bench, "traffic", f"{name}.json")
        with open(p) as f:
            t = json.load(f)
        if t.get("corrupt_block"):
            t["corrupt_block"] = 2 * chunk
        t["warm_rounds"] = 2 * chunk
        with open(p, "w") as f:
            json.dump(t, f)
    return Spec(os.path.join(root, "BENCHMARK.json"), bench)
