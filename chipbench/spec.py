"""Lookup of the benchmark's parts by name.

``BENCHMARK.json`` at the checkout root names every cell, configuration
and metric.  Each part lives in a file of its own, found by its name:

* a configuration at the ``file`` its entry gives (``configs/<name>.json``);
* a traffic mix at ``traffic/<name>.json`` under the benchmark directory,
  and the shape of its load, the mix's ``loop``, at ``loops/<loop>.py``;
* the system under test, the configuration's ``solver.entry``, at
  ``systems/<entry>.py``, a module with ``build(...)``;
* a metric's reader at ``metrics/<name>.py`` under the benchmark directory,
  a module with ``read(run) -> float | None``;
* a configuration's plain reference at ``references/<name>.py``: its
  operator, its preconditioners and its solver.

A later change adds a cell, configuration, mix, arrival shape, entry
point, preconditioner or metric by adding files and entries; nothing here
changes.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class Spec:
    """``BENCHMARK.json`` and the files it names.

    ``root`` holds ``BENCHMARK.json`` and the configuration files;
    ``bench_dir`` the ``traffic/``, ``loops/``, ``systems/``, ``metrics/``
    and ``references/`` directories.
    """

    def __init__(self, root=ROOT, bench_dir=BENCH_DIR):
        self.root = pathlib.Path(root)
        self.bench_dir = pathlib.Path(bench_dir)
        self.data = json.loads((self.root / "BENCHMARK.json").read_text())

    def cell(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        known = ", ".join(w["name"] for w in self.data["workloads"])
        raise KeyError(f"no workload {name!r} in BENCHMARK.json ({known})")

    def config(self, name: str) -> dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        path = self.bench_dir / "traffic" / f"{name}.json"
        if not path.is_file():
            raise KeyError(f"no traffic mix {name!r} ({path} missing)")
        return json.loads(path.read_text())

    def _module(self, kind: str, name: str):
        path = self.bench_dir / kind / f"{name}.py"
        if not path.is_file():
            raise KeyError(f"no {kind} module {name!r} ({path} missing)")
        spec = importlib.util.spec_from_file_location(
            f"chipbench_{kind}_{name.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def metric(self, name: str):
        """The reader module of metric ``name``."""
        return self._module("metrics", name)

    def reference(self, name: str):
        """The plain reference module ``name``."""
        return self._module("references", name)

    def system(self, entry: str):
        """The module that drives the program's entry point ``entry``."""
        return self._module("systems", entry)

    def loop(self, name: str):
        """The module that offers a mix's load in the shape ``name``."""
        return self._module("loops", name)

    def metrics_for(self, cell: str, trace: bool) -> list[dict]:
        """The metric entries a run of ``cell`` reports: its end-to-end
        metrics with ``trace`` off, its per-layer metrics with it on."""
        e2e = [m for m in self.data["end_to_end"]
               if cell in m.get("workloads", [cell])]
        if not trace:
            return e2e
        mine = {m["name"] for m in e2e}
        return [m for m in self.data["per_layer"]
                if (cell in m["workloads"] if "workloads" in m
                    else m["moves"] in mine)]
