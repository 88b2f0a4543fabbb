"""Find the benchmark's parts by name.

Everything that belongs to one configuration, one traffic mix, one
per-layer metric or one cell's correctness limits sits in a file of its
own, found from the name `BENCHMARK.json` gives it:

  configs/<config>.json     the deployment as it is run
  configs/<config>.py       `scenario()`: the program's name for it
  traffic/<traffic>.json    parameters the one generator reads; its
                            "entry" names its entry point
  entries/<entry>.py        `Entry`: the entry point's timed path, its
                            sample, reference and comparison
  metrics/<metric>.py       `read(ctx)`: the metric, or None; a metric
                            `<stem>.<split>` without a file of its own is
                            read by `metrics/<stem>.py`
  limits/<workload>.json    the limit of each number `correct` compares

Adding a cell, a mix, a configuration or a metric is adding files and
entries: nothing here names any of them.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Callable, Dict, List

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Cell:
    """One entry of `workloads`, with its files loaded."""

    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    limits: Dict[str, float | None]
    bench_dir: pathlib.Path

    def scenario(self) -> str:
        """The program's scenario name for this deployment, registered by
        the configuration's own module where the program lacks it."""
        return load_module(self.bench_dir / "configs"
                           / f"{self.config_name}.py").scenario()

    def entry(self) -> type:
        """The class that drives the mix's entry point."""
        return load_module(self.bench_dir / "entries"
                           / f"{self.traffic['entry']}.py").Entry

    def metric_reader(self, name: str) -> Callable:
        own = self.bench_dir / "metrics" / f"{name}.py"
        shared = self.bench_dir / "metrics" / f"{name.split('.')[0]}.py"
        return load_module(own if own.exists() else shared).read


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{path.parent.name}_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT,
              bench_dir: pathlib.Path = BENCH_DIR) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    try:
        w = next(w for w in spec["workloads"] if w["name"] == name)
    except StopIteration:
        known = [w["name"] for w in spec["workloads"]]
        raise SystemExit(f"unknown workload {name!r}; known: {known}")
    return cell_of(spec, w, root, bench_dir)


def cell_of(spec: dict, w: dict, root: pathlib.Path = ROOT,
            bench_dir: pathlib.Path = BENCH_DIR) -> Cell:
    """The cell of workload entry `w` under the benchmark `spec`."""
    name = w["name"]
    conf = next(c for c in spec["configs"] if c["name"] == w["config"])
    limits_path = bench_dir / "limits" / f"{name}.json"
    limits = (json.loads(limits_path.read_text())["limits"]
              if limits_path.exists() else {})
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        config=json.loads((root / conf["file"]).read_text()),
        traffic_name=w["traffic"],
        traffic=json.loads((bench_dir / "traffic"
                            / f"{w['traffic']}.json").read_text()),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
        limits={k: v["limit"] for k, v in limits.items()},
        bench_dir=bench_dir)
