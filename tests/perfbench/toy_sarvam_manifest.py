"""The rehearsal's manifest for the latent, expert-layer SERVING driver:
``toy_manifest.build`` over ``toy_sarvam/cells.json`` (the toy
configuration and cell, and the real cell it stands for; every metric
that lists the real cell is taken over listing the toy one)."""
import json
import os

import toy_manifest

ROOT = toy_manifest.ROOT
TOY = os.path.join(toy_manifest.HERE, "toy_sarvam")


def build() -> dict:
    kept, toy_manifest.TOY = toy_manifest.TOY, TOY
    try:
        return toy_manifest.build()
    finally:
        toy_manifest.TOY = kept


def write(directory) -> str:
    path = os.path.join(str(directory), "BENCHMARK.json")
    with open(path, "w") as f:
        json.dump(build(), f, indent=1)
    return path
