"""Find the benchmark's pieces by name, so that adding one is adding a file.

    configs/<config>.json        sizes, server settings, weight seed
    mixes/<traffic>.json         parameters of one traffic mix
    metrics/<metric>.py          reader of one per-layer metric: read(run)
    kernels/<kernel>.py          operations and bytes of one kernel's call
    references/<name>.py         plain reference forward of an architecture

``BENCH_DIR`` is this benchmark's directory; every loader also takes a
``root`` so a test can point it at a directory of its own.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
REPO_DIR = BENCH_DIR.parent


def _path(kind: str, name: str, suffix: str, root: Optional[Path]) -> Path:
    path = Path(root or BENCH_DIR) / kind / f"{name}{suffix}"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    return path


def load_json(kind: str, name: str, root: Optional[Path] = None) -> dict:
    return json.loads(_path(kind, name, ".json", root).read_text())


def load_json_file(name: str, root: Optional[Path] = None) -> dict:
    return json.loads((Path(root or BENCH_DIR) / name).read_text())


def load_module(kind: str, name: str, root: Optional[Path] = None) -> ModuleType:
    path = _path(kind, name, ".py", root)
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def names(kind: str, suffix: str, root: Optional[Path] = None) -> list:
    d = Path(root or BENCH_DIR) / kind
    return sorted(p.name[: -len(suffix)] for p in d.glob(f"*{suffix}"))


def benchmark(repo: Optional[Path] = None) -> dict:
    return json.loads((Path(repo or REPO_DIR) / "BENCHMARK.json").read_text())


def workload(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload named {name!r} in BENCHMARK.json")


def metrics_for(spec: dict, section: str, workload_name: str) -> list:
    """The metrics of ``section`` ("end_to_end" or "per_layer") that the
    cell reports: those without a ``workloads`` list, and those whose list
    names it."""
    return [m for m in spec[section]
            if workload_name in m.get("workloads", [workload_name])]
