"""Find a cell's parts by name, under the root of a checkout.

``BENCHMARK.json`` names each workload's configuration and traffic mix; the
rest is found by name under ``benchmarks/chip/``:

* a configuration: the ``file`` its entry in ``BENCHMARK.json`` gives;
* its family (reference, weights, FLOPs): ``families/<family>.py``;
* a traffic mix: ``traffic/<traffic>.json``;
* the limits of the comparison: ``limits/<workload>.json``;
* a per-layer metric: ``metrics/<name>.py``, whose ``read(record)`` returns
  a number or None;
* peaks per ``device_kind``: ``peaks.json``.

Adding a configuration, a mix or a metric adds files and entries only.

Staged for the four-chip cell ``phi4-mini-3.8b-L12.2x2.b8s1k``, which is not
in ``BENCHMARK.json`` until it has run on the chip:
``configs/phi4-mini-3.8b-L12.json``, ``traffic/2x2.b8s1k.json``,
``metrics/collective_exposed_ms.py`` and the ``no_exchange`` fault.  Until
then only the tests reach them.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
from typing import Callable, List, Tuple

HERE = "benchmarks/chip"


def _json(path: pathlib.Path):
    with open(path) as f:
        return json.load(f)


def _module(path: pathlib.Path):
    if not path.is_file():
        raise FileNotFoundError(f"benchmark: no {path}")
    spec = importlib.util.spec_from_file_location(
        f"_bench_{path.parent.name}_{path.stem}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    def __init__(self, root, workload: str):
        self.root = pathlib.Path(root)
        self.bench = _json(self.root / "BENCHMARK.json")
        found = [w for w in self.bench["workloads"] if w["name"] == workload]
        if not found:
            raise KeyError(f"benchmark: no workload {workload!r}")
        self.workload = found[0]
        self.name = workload
        self.chips = int(self.workload["chips"])
        (entry,) = [c for c in self.bench["configs"]
                    if c["name"] == self.workload["config"]]
        self.config = _json(self.root / entry["file"])
        self.traffic = _json(self.dir / "traffic" /
                             f"{self.workload['traffic']}.json")
        self.limits = _json(self.dir / "limits" / f"{workload}.json")
        self.family = _module(self.dir / "families" /
                              f"{self.config['family']}.py")

    @property
    def dir(self) -> pathlib.Path:
        return self.root / HERE

    def metrics(self, trace: bool) -> List[dict]:
        """The metric entries this cell reports: end-to-end ones without a
        trace, per-layer ones with it."""
        group = self.bench["per_layer" if trace else "end_to_end"]
        return [m for m in group
                if self.name in m.get("workloads", [self.name])]

    def readers(self) -> List[Tuple[dict, Callable]]:
        return [(m, _module(self.dir / "metrics" / f"{m['name']}.py").read)
                for m in self.metrics(trace=True)]

    def peaks(self, device_kind: str) -> dict:
        table = _json(self.dir / "peaks.json")
        if device_kind not in table:
            raise KeyError(f"benchmark: no peaks for device kind "
                           f"{device_kind!r} in peaks.json")
        return table[device_kind]
