"""Check ``BENCHMARK.json`` and the data files it names before anything
runs.  ``python -m perfbench.validate`` prints the faults and exits 1 if
there are any; ``run.py`` calls :func:`check` first.

What refused PR 22 is the first rule: the name of a cell, a
configuration, a metric AND a layer is made of letters, digits, ``_``,
``.`` and ``-`` alone.
"""
from __future__ import annotations

import json
import os
import re
import sys
from typing import Dict, List

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
WORKLOAD_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}
METRICS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "metrics")
WIDTH = re.compile(r"(_dim|_rank|(hidden|intermediate|latent|state|head|ffn"
                   r"|proj\w*)_(size|width)|expan\w*|experts_per_tok\w*"
                   r"|^d_model|^d_ff)$")


def reader_path(metric: str) -> str:
    """The file that reads a per-layer metric: ``metrics/<name>.py``,
    or, for a quantity split by the end-to-end metric it moves
    (``mfu.train``, ``mfu.serve``), ``metrics/<name before the last
    dot>.py`` where the split name has no file of its own."""
    own = os.path.join(METRICS_DIR, metric + ".py")
    if os.path.isfile(own) or "." not in metric:
        return own
    return os.path.join(METRICS_DIR, metric.rsplit(".", 1)[0] + ".py")


def _line(text, what: str, faults: List[str]) -> None:
    if not isinstance(text, str) or not 1 <= len(text) <= 200 \
            or "\n" in text or "\t" in text:
        faults.append("%s: 1 to 200 characters on one line, no tab" % what)


def _name(text, what: str, faults: List[str]) -> None:
    if not isinstance(text, str) or not NAME.match(text):
        faults.append("%s %r: letters, digits, '_', '.', '-' only, at "
                      "most 64, not starting with '.' or '-'" % (what, text))


def _keys(entry: Dict, wanted, what: str, faults: List[str],
          optional=()) -> None:
    extra = set(entry) - set(wanted) - set(optional)
    missing = set(wanted) - set(entry)
    if extra or missing:
        faults.append("%s: keys missing %s, not allowed %s"
                      % (what, sorted(missing), sorted(extra)))


def _metric(m: Dict, what: str, faults: List[str]) -> None:
    _name(m.get("name"), what, faults)
    if not isinstance(m.get("unit"), str) or not UNIT.match(m["unit"]):
        faults.append("%s: unit %r" % (what, m.get("unit")))
    if m.get("better") not in ("lower", "higher"):
        faults.append("%s: better is 'lower' or 'higher'" % what)
    if m.get("source") not in SOURCES:
        faults.append("%s: source is one of %s" % (what, SOURCES))


def check(manifest: Dict, root: str, data_root: str) -> List[str]:
    """Every fault found, as a line of text; empty when the manifest
    and its files are sound.  ``root`` is the checkout, ``data_root``
    the directory that holds ``workloads/``; the metrics' readers are
    code and always ``perfbench/metrics/``."""
    faults: List[str] = []
    _keys(manifest, TOP_KEYS, "BENCHMARK.json", faults)
    if faults:
        return faults
    paths = manifest["paths"]
    if not isinstance(paths, list) or not 1 <= len(paths) <= 16 or \
            not all(isinstance(p, str) and PATH.match(p)
                    and not p.startswith("/") and ".." not in p.split("/")
                    for p in paths):
        faults.append("paths: 1 to 16 relative directories")
        return faults
    command = manifest["command"]
    if not isinstance(command, list) or not 1 <= len(command) <= 32:
        faults.append("command: a list of 1 to 32 strings")
    else:
        for word in command:
            _line(word, "command word %r" % (word,), faults)
            if isinstance(word, str) and (word.startswith("/")
                                          or ".." in word.split("/")):
                faults.append("command word %r leaves the checkout" % word)
    secs = manifest["run_seconds"]
    if not isinstance(secs, int) or isinstance(secs, bool) \
            or not 1 <= secs <= 51:
        faults.append("run_seconds: a whole number from 1 to 51")

    def under_paths(f: str) -> bool:
        return any(f == p or f.startswith(p.rstrip("/") + "/")
                   for p in paths)

    # -- configurations ------------------------------------------------
    configs = {}
    files = set()
    if not 1 <= len(manifest["configs"]) <= 24:
        faults.append("configs: 1 to 24")
    for c in manifest["configs"]:
        what = "config %r" % (c.get("name"),)
        _keys(c, CONFIG_KEYS, what, faults)
        _name(c.get("name"), "config", faults)
        _line(c.get("source"), what + " source", faults)
        _line(c.get("why"), what + " why", faults)
        if c.get("name") in configs:
            faults.append(what + ": named twice")
        configs[c.get("name")] = c
        f = c.get("file")
        if not isinstance(f, str) or not PATH.match(f) \
                or not under_paths(f) or f in files:
            faults.append(what + ": file %r must lie under paths and "
                          "belong to this configuration alone" % (f,))
            continue
        files.add(f)
        if not os.path.isfile(os.path.join(root, f)):
            faults.append(what + ": file %s does not exist" % f)
        reduced = c.get("reduced")
        if not isinstance(reduced, list) or len(reduced) > 16:
            faults.append(what + ": reduced is a list of at most 16 keys")
            continue
        for key in reduced:
            _name(key, what + " reduced key", faults)
            if isinstance(key, str) and WIDTH.search(key):
                faults.append(what + ": reduced may not name a width "
                              "(%s)" % key)

    # -- cells ---------------------------------------------------------
    cells = {}
    pairs = set()
    if not 1 <= len(manifest["workloads"]) <= 24:
        faults.append("workloads: 1 to 24")
    for w in manifest["workloads"]:
        what = "workload %r" % (w.get("name"),)
        _keys(w, WORKLOAD_KEYS, what, faults)
        for key in ("name", "config", "traffic"):
            _name(w.get(key), what + " " + key, faults)
        _line(w.get("why"), what + " why", faults)
        if w.get("chips") not in (1, 4):
            faults.append(what + ": chips is 1 or 4")
        if w.get("config") not in configs:
            faults.append(what + ": unknown config %r" % (w.get("config"),))
        if w.get("name") in cells:
            faults.append(what + ": named twice")
        pair = (w.get("config"), w.get("traffic"))
        if pair in pairs:
            faults.append(what + ": config and traffic appear twice")
        pairs.add(pair)
        cells[w.get("name")] = w
        f = os.path.join(data_root, "workloads", "%s.json" % w.get("name"))
        if not os.path.isfile(f):
            faults.append(what + ": no file %s" % f)
    used = {w.get("config") for w in manifest["workloads"]}
    for name in configs:
        if name not in used:
            faults.append("config %r is used by no cell" % name)
    four = sum(1 for w in manifest["workloads"] if w.get("chips") == 4)
    if four > max(1, len(manifest["workloads"]) // 4):
        faults.append("too many four-chip cells: %d" % four)

    # -- metrics -------------------------------------------------------
    def cells_of(m: Dict, what: str) -> List[str]:
        listed = m.get("workloads")
        if listed is None:
            return list(cells)
        if not isinstance(listed, list) or not listed:
            faults.append(what + ": workloads is a list of cells")
            return []
        for name in listed:
            if name not in cells:
                faults.append(what + ": unknown cell %r" % (name,))
        return [n for n in listed if n in cells]

    e2e: Dict[str, List[str]] = {}
    names = set()
    if not 1 <= len(manifest["end_to_end"]) <= 16:
        faults.append("end_to_end: 1 to 16 metrics")
    for m in manifest["end_to_end"]:
        what = "end_to_end metric %r" % (m.get("name"),)
        _keys(m, E2E_KEYS, what, faults, optional=("workloads",))
        _metric(m, what, faults)
        if m.get("source") not in ("host_clock", "device_trace"):
            faults.append(what + ": source is host_clock or device_trace")
        bound = m.get("bound")
        if not isinstance(bound, (int, float)) or isinstance(bound, bool) \
                or not 0.01 <= bound <= 0.1:
            faults.append(what + ": bound from 0.01 to 0.1")
        if m.get("name") in names:
            faults.append(what + ": named twice")
        names.add(m.get("name"))
        e2e[m.get("name")] = cells_of(m, what)
    if "setup_s" not in e2e:
        faults.append("end_to_end: setup_s is missing")
    elif set(e2e["setup_s"]) != set(cells):
        faults.append("setup_s: every cell reports it")
    if not 1 <= len(manifest["per_layer"]) <= 128:
        faults.append("per_layer: 1 to 128 metrics")
    layered = set()
    for m in manifest["per_layer"]:
        what = "per_layer metric %r" % (m.get("name"),)
        _keys(m, LAYER_KEYS, what, faults, optional=("workloads",))
        _metric(m, what, faults)
        _name(m.get("layer"), what + " layer", faults)
        if m.get("name") in names:
            faults.append(what + ": named twice")
        names.add(m.get("name"))
        moves = m.get("moves")
        if moves not in e2e:
            faults.append(what + ": moves %r, which is no end-to-end "
                          "metric" % (moves,))
            continue
        mine = cells_of(m, what)
        for cell in mine:
            if cell not in e2e[moves]:
                faults.append(what + ": cell %s does not report %s"
                              % (cell, moves))
        layered.update(mine)
        reader = reader_path(str(m.get("name")))
        if not os.path.isfile(reader):
            faults.append(what + ": no reader %s" % reader)
    for cell in cells:
        if not any(cell in v for k, v in e2e.items() if k != "setup_s"):
            faults.append("cell %s reports no end-to-end metric besides "
                          "setup_s" % cell)
        if cell not in layered:
            faults.append("cell %s reports no per-layer metric" % cell)
    return faults


def load(root: str) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def main(argv=None) -> int:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    faults = check(load(root), root, os.path.join(root, "perfbench"))
    for line in faults:
        print("BENCHMARK.json: " + line, file=sys.stderr)
    return 1 if faults else 0


if __name__ == "__main__":
    sys.exit(main())
