"""A benchmark root at a size a test run holds: the repository's own
generators, traffic mixes, compares, metric readers and peaks, its cells
and metrics, and each configuration file shrunk (scale 10 Graph500, the
four-chip configuration on one device in sim mode unless `dist`)."""
from __future__ import annotations

import json
import pathlib
import shutil

REPO = pathlib.Path(__file__).resolve().parents[2]
CPU_DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def make_root(tmp: pathlib.Path, *, dist: bool = False) -> pathlib.Path:
    (tmp / "bench").mkdir(parents=True, exist_ok=True)
    for part in ("generators", "traffic", "compares", "metrics"):
        shutil.copytree(REPO / "bench" / part, tmp / "bench" / part, dirs_exist_ok=True,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "bench" / "peaks.json", tmp / "bench" / "peaks.json")
    (tmp / "bench" / "configs").mkdir(exist_ok=True)
    for path in (REPO / "bench" / "configs").glob("*.json"):
        cfg = json.loads(path.read_text())
        cfg["generator"]["scale"] = 10
        if cfg["mode"] == "dist" and not dist:
            cfg.update(mode="sim", chips=1)
        (tmp / "bench" / "configs" / path.name).write_text(json.dumps(cfg))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    if not dist:
        for cell in spec["workloads"]:
            cell["chips"] = 1
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp
