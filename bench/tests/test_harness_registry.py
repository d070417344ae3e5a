"""Pieces are found by name: a configuration, a mix or a metric added as a
file is found with no edit to any other file."""
import json

import _paths  # noqa: F401

from harness import registry


def test_new_files_are_found_by_name(tmp_path):
    for kind in ("configs", "mixes", "metrics", "kernels"):
        (tmp_path / kind).mkdir()
    (tmp_path / "configs" / "toy.cfg.json").write_text(
        json.dumps({"model": "toy", "hidden_size": 8}))
    (tmp_path / "mixes" / "toy-mix.json").write_text(
        json.dumps({"kind": "batch", "requests": 3}))
    (tmp_path / "metrics" / "toy_ms.open.py").write_text(
        "def read(run):\n    return run['x'] * 2\n")
    (tmp_path / "kernels" / "toy_kernel.py").write_text(
        "TRACE_NAME = 'toy'\n")
    assert registry.load_json("configs", "toy.cfg", tmp_path)["model"] == "toy"
    assert registry.load_json("mixes", "toy-mix", tmp_path)["requests"] == 3
    m = registry.load_module("metrics", "toy_ms.open", tmp_path)
    assert m.read({"x": 4}) == 8
    assert registry.names("kernels", ".py", tmp_path) == ["toy_kernel"]


def test_benchmark_names_resolve_to_files():
    spec = registry.benchmark()
    for w in spec["workloads"]:
        registry.load_json("mixes", w["traffic"])
        registry.load_json("limits", w["name"])
    for c in spec["configs"]:
        cfg = registry.load_json("configs", c["name"])
        registry.load_module("references", cfg["reference"])
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(registry.load_module("metrics", m["name"]).read)


def test_metrics_follow_their_workloads_key():
    spec = {"end_to_end": [{"name": "a"}, {"name": "b", "workloads": ["x"]}]}
    names = [m["name"] for m in registry.metrics_for(spec, "end_to_end", "y")]
    assert names == ["a"]
    names = [m["name"] for m in registry.metrics_for(spec, "end_to_end", "x")]
    assert names == ["a", "b"]
