"""Find the benchmark's parts by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix, per-layer
metric or path driver is a file of its own, found by name:

  * a configuration: the ``file`` its ``configs`` entry names (JSON), with
    its plain reference beside it (the same path, ``.py``);
  * a traffic mix: ``bench/traffic/<name>.json``;
  * a per-layer metric: ``bench/metrics/<name>.py`` (a split name
    ``<quantity>.<part>`` may share ``<quantity>.py``), whose
    ``read(run)`` returns the number, or None where the run has nothing
    to read;
  * a path driver: ``bench/drivers/<config["driver"]>.py``, whose
    ``run(ctx)`` sets up, measures the window and checks the outputs.

So a new configuration, mix or metric is new files plus new
``BENCHMARK.json`` entries; no existing file changes.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType
from typing import List, Optional

BENCH = Path(__file__).resolve().parent


_LOADED = {}


def load_module(path: Path, name: Optional[str] = None) -> ModuleType:
    """Import one file by path (names may hold dots and dashes), once per
    process: a second load of the same file gives the same module."""
    key = Path(path).resolve()
    if key in _LOADED:
        return _LOADED[key]
    spec = importlib.util.spec_from_file_location(
        name or "bench_" + path.stem.replace(".", "_").replace("-", "_"),
        path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    _LOADED[key] = mod
    return mod


class Benchmark:
    """``BENCHMARK.json`` at ``root`` and the files it names."""

    def __init__(self, root: Path):
        self.root = Path(root)
        self.bench = self.root / "bench"
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def _entry(self, key: str, name: str) -> dict:
        for e in self.spec[key]:
            if e["name"] == name:
                return e
        raise KeyError(f"no {key} entry named {name!r} in BENCHMARK.json")

    def cell(self, name: str) -> dict:
        return self._entry("workloads", name)

    def config(self, name: str) -> dict:
        entry = self._entry("configs", name)
        data = json.loads((self.root / entry["file"]).read_text())
        return {**data, "name": name}

    def reference(self, config_name: str) -> ModuleType:
        """The configuration's plain reference: its file, ``.py``."""
        path = self.root / self._entry("configs", config_name)["file"]
        return load_module(path.with_suffix(".py"))

    def traffic(self, name: str) -> dict:
        data = json.loads((self.bench / "traffic" / f"{name}.json")
                          .read_text())
        return {**data, "name": name}

    def driver(self, kind: str) -> ModuleType:
        return load_module(self.bench / "drivers" / f"{kind}.py")

    def end_to_end(self, cell: str) -> List[dict]:
        """The end-to-end metrics this cell reports."""
        return [m for m in self.spec["end_to_end"]
                if cell in m.get("workloads", [cell])]

    def per_layer(self, cell: str) -> List[dict]:
        """The per-layer metrics this cell reports: those listing it, and
        those without a list whose ``moves`` metric the cell reports."""
        e2e = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.spec["per_layer"]
                if cell in m.get("workloads", ())
                or ("workloads" not in m and m["moves"] in e2e)]

    def reader(self, metric: str):
        """``metrics/<metric>.py``; a metric split by the end-to-end metric
        it moves, ``<quantity>.<part>``, without a file of its own is read
        by ``metrics/<quantity>.py``."""
        path = self.bench / "metrics" / f"{metric}.py"
        if not path.exists() and "." in metric:
            path = path.with_name(metric.rsplit(".", 1)[0] + ".py")
        return load_module(path).read

