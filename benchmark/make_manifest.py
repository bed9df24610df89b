"""Writes ``BENCHMARK.json`` from the benchmark's own files.

A cell names its metrics; no metric file lists cells. A later PR adds a
cell, a configuration or a metric as new files and runs this script: the
cells that ``BENCHMARK.json`` already lists keep their order, each new
file under ``workloads/`` is appended, and its name joins the ``workloads``
lists of the metrics it reports.

    python benchmark/make_manifest.py            # rewrite BENCHMARK.json
    python benchmark/make_manifest.py --check    # exit 1 if it would change
"""
from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
RUN_SECONDS = 51


def load(kind, name):
    with open(os.path.join(HERE, kind, name + ".json")) as fh:
        return json.load(fh)


def manifest():
    path = os.path.join(ROOT, "BENCHMARK.json")
    cells = []
    if os.path.exists(path):
        with open(path) as fh:
            cells = [w["name"] for w in json.load(fh)["workloads"]]
    for f in sorted(os.listdir(os.path.join(HERE, "workloads"))):
        name = f[:-len(".json")]
        if name not in cells:
            cells.append(name)
    configs, workloads, e2e, per_layer = {}, [], {}, {}
    for name in cells:
        cell = load("workloads", name)
        cfg = load("configs", cell["config"])
        configs.setdefault(cell["config"], {
            "name": cell["config"], "source": cfg["source"],
            "file": f"benchmark/configs/{cell['config']}.json",
            "reduced": cfg["reduced"], "why": cfg["why"]})
        workloads.append({
            "name": name, "config": cell["config"],
            "traffic": name.split(".", 1)[1], "chips": cell["chips"],
            "why": cell["why"]})
        for m in cell["end_to_end"]:
            spec = load("metrics", m)
            entry = e2e.setdefault(m, {
                "name": m, "unit": spec["unit"], "better": spec["better"],
                "bound": spec["bound"], "source": spec["source"]})
            if m != "setup_s":
                entry.setdefault("workloads", []).append(name)
        for m in cell["per_layer"]:
            spec = load("metrics", m)
            per_layer.setdefault(m, {
                "name": m, "unit": spec["unit"], "better": spec["better"],
                "source": spec["source"], "layer": spec["layer"],
                "moves": spec["moves"], "workloads": []})[
                    "workloads"].append(name)
    return {"command": ["python3", "benchmark/run.py"],
            "paths": ["benchmark"], "run_seconds": RUN_SECONDS,
            "configs": list(configs.values()), "workloads": workloads,
            "end_to_end": list(e2e.values()),
            "per_layer": list(per_layer.values())}


def main():
    text = json.dumps(manifest(), indent=1) + "\n"
    path = os.path.join(ROOT, "BENCHMARK.json")
    if "--check" in sys.argv:
        with open(path) as fh:
            return 0 if fh.read() == text else 1
    with open(path, "w") as fh:
        fh.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
