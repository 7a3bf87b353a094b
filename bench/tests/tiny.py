"""A copy of the benchmark's spec at sizes a CPU test can hold: the same
cells, traffic and readers, with each configuration cut down, plus the
cells whose files are in `bench/` but which are not in `BENCHMARK.json`
yet (`waiting.json`; PERF.md, Open questions)."""
import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY = {"kmedoid_tinyimg": {"n": 256, "d": 32, "k": 12, "classes": 8},
        "kcover_retail": {"n": 1024, "universe": 2000, "k": 12}}


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def tiny_root(dest: str) -> str:
    """Write the tiny spec under `dest` and return it."""
    bench = _load(os.path.join(ROOT, "BENCHMARK.json"))
    waiting = _load(os.path.join(ROOT, "bench", "tests", "waiting.json"))
    for key in ("configs", "workloads", "per_layer"):
        bench[key] += waiting[key]
    extra = [w["name"] for w in waiting["workloads"]]
    for m in bench["per_layer"]:
        if m["name"] in waiting["shared"]:
            m["workloads"] = m["workloads"] + extra
    os.makedirs(os.path.join(dest, "bench", "configs"))
    for c in bench["configs"]:
        cfg = dict(_load(os.path.join(ROOT, c["file"])), **TINY[c["name"]])
        with open(os.path.join(dest, c["file"]), "w") as f:
            json.dump(cfg, f)
    for sub in ("traffic", "references", "metrics", "generators", "paths"):
        shutil.copytree(os.path.join(ROOT, "bench", sub),
                        os.path.join(dest, "bench", sub),
                        ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return dest
