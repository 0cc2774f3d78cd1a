"""Finds a cell's data files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix, one cell
or one per-layer metric is a file of its own:

    BENCHMARK.json                         the index (cells, metrics, bounds)
    benchmarks/configs/<config>.json       sizes, source, deployment
    benchmarks/traffic/<traffic>.json      generator kind + parameters
    benchmarks/workloads/<cell>.json       runner kind, launch flags, check
    benchmarks/metrics/<metric>.json       the reader of one per-layer metric

A later PR adds a cell by adding files and entries; nothing under
``benchmarks/lib`` names a cell, a configuration, a metric or a model
family. Three kinds of code are named by data files, by path, and
loaded by ``benchmarks/lib/modules.py``:

- a configuration's ``reference``: a module with ``make_loss_fn(config,
  *, wrong, with_gradients, **check_sizes)``, ``make_logits_fn(config,
  *, wrong, **check_sizes)`` and ``GAIN_KEYS``; every ``wrong=`` name a
  cell lists under ``wrong_variants`` is one it must offer, so that the
  cell's tolerance is shown to reject something;
- a ``roofline_share`` reader's ``cost_module`` (default
  ``benchmarks/lib/costs.py``) with the ``cost_function`` it names, and
  the functions a configuration's ``cost_inputs`` name for the MFU line;
- nothing else: the model itself is built by the program from the
  configuration's keys (``benchmarks/lib/program.py``).

A path in a data file is looked up in the tree given by ``--root`` and
then in the checkout (``Spec.find``). The one change to an existing
line that a new cell needs: its name appended to the ``workloads`` list
of each metric of ``BENCHMARK.json`` that it reports.

Counter names a ``counter`` reader may give as ``key``: the runner's own
(``steps``; ``decode_steps``, ``prefill_calls``, ``live_tokens_mean``),
every end-to-end value and ``setup_s``, ``memory_peak_bytes``, and the
program's own: ``step.<name>`` for every scalar of the last training
step's metrics, ``engine.<name>`` for every number of the engine's
``metrics.snapshot()``, as its change over the window.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List

BENCH_DIR = "benchmarks"


class Refused(Exception):
    """The run cannot be made as its files stand (exit code 2, no
    result line): the message names what is missing."""


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

    def find(self, relative: str) -> str | None:
        """The file a data file names by its path: in this tree, else in
        the checkout that holds the harness; ``None`` where neither has
        it."""
        for root in (self.root, default_root()):
            candidate = os.path.join(root, relative)
            if os.path.isfile(candidate):
                return candidate
        return None

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
