"""Finding the benchmark's data files and code by name.

``BENCHMARK.json`` (one directory above this tree) names cells,
configurations and metrics. Everything that belongs to one of them sits in
a file of its own, found by that name:

- ``workloads/<cell>.json``      what the cell's check samples
- ``configs/<config>.json``      the deployment as it is run (``file`` in
                                 ``BENCHMARK.json``), naming its
                                 ``deployment`` builder and ``reference``
- ``traffic/<mix>.json``         a traffic mix: ``kind`` + parameters
- ``layer_metrics/<name>.json``  one per-layer metric: ``reader`` + params

and the code a name stands for is a module in a registry directory:
``deployments/``, ``references/``, ``traffic_kinds/``, ``readers/``. A later
PR adds files and appends entries; no file here is edited for a new cell.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
import sys

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
_MODULES: dict = {}


class SpecError(ValueError):
    """The benchmark's data files do not describe a runnable cell."""


def _load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


class Spec:
    def __init__(self, root: str):
        #: the checkout: holds BENCHMARK.json and the benchmark's tree
        self.root = os.path.abspath(root)
        path = os.path.join(self.root, "BENCHMARK.json")
        if not os.path.isfile(path):
            raise SpecError(f"no BENCHMARK.json in {self.root}")
        self.benchmark = _load_json(path)
        #: the benchmark's own tree (the first of ``paths``)
        self.tree = os.path.join(self.root, self.benchmark["paths"][0])

    # ------------------------------------------------------------- data

    def _entry(self, section: str, name: str) -> dict:
        for e in self.benchmark[section]:
            if e["name"] == name:
                return e
        raise SpecError(
            f"{name!r} is not in BENCHMARK.json {section!r}: "
            f"{[e['name'] for e in self.benchmark[section]]}"
        )

    def data(self, kind: str, name: str) -> dict:
        if not _NAME.match(name):
            raise SpecError(f"{name!r} is not a name")
        return _load_json(os.path.join(self.tree, kind, name + ".json"))

    def cell(self, name: str) -> dict:
        cell = dict(self._entry("workloads", name))
        cell.update(self.data("workloads", name))
        return cell

    def config(self, name: str) -> dict:
        entry = self._entry("configs", name)
        cfg = _load_json(os.path.join(self.root, entry["file"]))
        cfg["name"] = name
        return cfg

    def traffic(self, name: str) -> dict:
        mix = self.data("traffic", name)
        mix["name"] = name
        return mix

    def end_to_end(self, cell: str) -> list:
        """The end-to-end metrics ``cell`` reports: those that list it,
        and those with no ``workloads`` key."""
        return [
            m for m in self.benchmark["end_to_end"]
            if cell in m.get("workloads", [cell])
        ]

    def per_layer(self, cell: str) -> list:
        """The per-layer metrics a ``--trace 1`` run of ``cell`` reads:
        those that list the cell, and those with no ``workloads`` key
        whose end-to-end metric the cell reports."""
        reported = {m["name"] for m in self.end_to_end(cell)}
        return [
            m for m in self.benchmark["per_layer"]
            if cell in m.get("workloads", [cell])
            and ("workloads" in m or m["moves"] in reported)
        ]

    # ------------------------------------------------------------- code

    def module(self, registry: str, name: str):
        """The module ``<tree>/<registry>/<name>.py`` — loaded from the
        file, so it is the benchmark's own whatever ``sys.path`` holds."""
        if not _NAME.match(name):
            raise SpecError(f"{name!r} is not a name")
        path = os.path.join(self.tree, registry, name + ".py")
        if path in _MODULES:
            return _MODULES[path]
        if not os.path.isfile(path):
            raise SpecError(
                f"no {registry} named {name!r} (looked for {path})"
            )
        modname = f"_benchmark_{registry}_{name}".replace("-", "_").replace(
            ".", "_"
        )
        spec = importlib.util.spec_from_file_location(modname, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[modname] = mod
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
        return mod
