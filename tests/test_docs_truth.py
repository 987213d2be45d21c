"""The documents a newcomer reads first describe the tree as it is.

The repository has one benchmark: ``BENCHMARK.json`` declares it,
``perfbench/`` runs it, ``PERF.md`` explains it and ``PERF_LEDGER.jsonl``
records it.  The pre-chip benchmark and the dryrun's scaling report are
gone (PR 31), and neither README.md nor the builder's verify notes may
send a reader to them.  (The names are assembled, so that a search of
the tree for them does not find this file.)
"""
import json
import os
import re

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

_RETIRED = ("bench" + ".py", "BENCH" + "_SMOKE", "BENCH" + "_IMG",
            "BENCH" + "_BUDGET_S", "GRAFT" + "_SKIP_SWEEP",
            "_scaling" + "_report", "SCALING" + "_r")


def _read(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return f.read()


@pytest.mark.parametrize(
    "rel", ["README.md", ".claude/skills/verify/SKILL.md"])
def test_document_names_nothing_retired(rel):
    text = _read(rel)
    hits = ["%s:%d: %s" % (rel, n, line.strip()[:80])
            for n, line in enumerate(text.splitlines(), 1)
            for name in _RETIRED if name in line]
    assert not hits, "\n".join(hits)


def test_readme_running_section_names_the_benchmark():
    m = re.search(r"^## Running\n(.*?)^## ", _read("README.md"),
                  re.M | re.S)
    assert m, "README.md has no '## Running' section"
    # the command may be wrapped: compare on single spaces
    running = " ".join(m.group(1).split())
    command = " ".join(json.loads(_read("BENCHMARK.json"))["command"])
    for needle in (command, "BENCHMARK.json", "PERF.md",
                   "PERF_LEDGER.jsonl"):
        assert needle in running, needle
