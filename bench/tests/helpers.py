"""A throwaway checkout for the benchmark's tests: BENCHMARK.json, the
benchmark's files and the program, with the configurations cut to the
apps' ``tiny`` inputs and 8 workers so that a cell runs on the host CPU."""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_ARGS = {"fib": {"n": 8}, "sort": {"levels": 4}, "uts": {"n_target": 120}}


def tiny_config(name: str, n_workers: int = 8) -> dict:
    with open(os.path.join(REPO, "bench", "configs", name + ".json")) as f:
        cfg = json.load(f)
    cfg["n_workers"] = n_workers
    for app in cfg["apps"]:
        app["args"] = TINY_ARGS[app["builder"]]
    return cfg


def make_root(tmp, cells=None, seconds=None) -> str:
    """A checkout under ``tmp`` whose configurations are tiny."""
    root = os.path.join(str(tmp), "checkout")
    os.makedirs(root)
    os.symlink(os.path.join(REPO, "src"), os.path.join(root, "src"))
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for c in bench["configs"]:
        with open(os.path.join(root, c["file"]), "w") as f:
            json.dump(tiny_config(c["name"]), f)
    if cells is not None:
        bench["workloads"] = [w for w in bench["workloads"]
                              if w["name"] in cells]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
