"""The rehearsal's manifest, made from the committed ``BENCHMARK.json``.

``toy/cells.json`` names the toy configurations and cells, which real
cell each toy training cell stands for, and the entries of the serving
metrics: the serving cell is out of ``BENCHMARK.json`` until it is
proven on a traffic mix with a public source (``PERF.md`` §7), and its
driver and readers are kept whole by the rehearsal meanwhile.  Every
metric of the real manifest is taken over as it stands, listing the toy
cells that stand for its real ones.
"""
import copy
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.abspath(os.path.join(HERE, "..", ".."))
TOY = os.path.join(HERE, "toy")


def build() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    with open(os.path.join(TOY, "cells.json")) as f:
        toy = json.load(f)
    stand_ins = {}
    for name, real in toy["stands_for"].items():
        stand_ins.setdefault(real, []).append(name)
    out = dict(manifest, run_seconds=1, configs=toy["configs"],
               workloads=toy["workloads"])
    every = [w["name"] for w in toy["workloads"]]
    for key in ("end_to_end", "per_layer"):
        out[key] = []
        for metric in manifest[key]:
            metric = copy.deepcopy(metric)
            if "workloads" in metric:
                metric["workloads"] = [t for real in metric["workloads"]
                                       for t in stand_ins.get(real, [])]
            if metric.get("workloads", every):
                out[key].append(metric)
        out[key] += toy[key]
    return out


def write(directory) -> str:
    path = os.path.join(str(directory), "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(build(), f, indent=1)
    return path
