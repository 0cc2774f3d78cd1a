"""Finds a cell's data files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix, one cell
or one per-layer metric is a file of its own:

    BENCHMARK.json                         the index (cells, metrics, bounds)
    benchmarks/configs/<config>.json       sizes, source, deployment
    benchmarks/traffic/<traffic>.json      generator kind + parameters
    benchmarks/workloads/<cell>.json       runner kind, launch flags, check
    benchmarks/metrics/<metric>.json       the reader of one per-layer metric

A later PR adds a cell by adding files and entries; nothing here names
a cell, a configuration or a metric.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List

BENCH_DIR = "benchmarks"

# config.json keys that the program's model configurations
# (``LlamaConfig``, the trainer's arguments) take under the same name
MODEL_SHAPE_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "head_dim", "rope_theta",
    "rms_norm_eps", "max_position_embeddings", "tie_word_embeddings",
)


def default_root() -> str:
    """The checkout: the directory that holds ``benchmarks/``."""
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def _load(path: str) -> Dict[str, Any]:
    with open(path) as f:
        obj = json.load(f)
    if not isinstance(obj, dict):
        raise ValueError(f"{path} does not hold a JSON object")
    return obj


class Spec:
    """``BENCHMARK.json`` plus the data files under ``root``."""

    def __init__(self, root: str | None = None) -> None:
        self.root = os.path.abspath(root or default_root())
        self.index = _load(os.path.join(self.root, "BENCHMARK.json"))

    def path(self, *parts: str) -> str:
        return os.path.join(self.root, *parts)

    def _entry(self, section: str, name: str) -> Dict[str, Any]:
        for entry in self.index[section]:
            if entry["name"] == name:
                return entry
        known = ", ".join(e["name"] for e in self.index[section])
        raise KeyError(f"BENCHMARK.json has no {section} entry {name!r} "
                       f"(it has: {known})")

    def workload(self, name: str) -> Dict[str, Any]:
        """The index entry of a cell merged over its own file."""
        entry = self._entry("workloads", name)
        cell = _load(self.path(BENCH_DIR, "workloads", f"{name}.json"))
        for key in ("config", "traffic", "chips"):
            if key in cell and cell[key] != entry[key]:
                raise ValueError(
                    f"cell {name}: {key} is {cell[key]!r} in its file and "
                    f"{entry[key]!r} in BENCHMARK.json")
        return {**cell, **entry}

    def config(self, name: str) -> Dict[str, Any]:
        entry = self._entry("configs", name)
        config = _load(self.path(entry["file"]))
        for key in entry.get("reduced", []):
            if key not in config.get("reduced", []):
                raise ValueError(
                    f"config {name}: {key!r} is reduced in BENCHMARK.json "
                    "and not in its file")
        return config

    def traffic(self, name: str) -> Dict[str, Any]:
        return _load(self.path(BENCH_DIR, "traffic", f"{name}.json"))

    def peaks(self, device_kind: str) -> Dict[str, Any]:
        table = _load(self.path(BENCH_DIR, "peaks.json"))
        if device_kind not in table["devices"]:
            raise KeyError(
                f"device_kind {device_kind!r} is not in "
                f"{BENCH_DIR}/peaks.json: add it with its source before "
                "measuring on it")
        return table["devices"][device_kind]

    def _metrics_of(self, section: str, cell: str) -> List[Dict[str, Any]]:
        return [m for m in self.index[section]
                if "workloads" not in m or cell in m["workloads"]]

    def end_to_end(self, cell: str) -> List[Dict[str, Any]]:
        return self._metrics_of("end_to_end", cell)

    def per_layer(self, cell: str) -> List[Dict[str, Any]]:
        """Index entries of the cell's per-layer metrics, each merged
        with its reader file."""
        out = []
        for entry in self._metrics_of("per_layer", cell):
            reader = _load(self.path(
                BENCH_DIR, "metrics", f"{entry['name']}.json"))
            out.append({**reader, **entry})
        return out
