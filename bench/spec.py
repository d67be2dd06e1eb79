"""Finding a cell's parts by name: `BENCHMARK.json` names the cell, its
configuration file and its metrics; a traffic mix `<mix>` lives in
`bench/traffic/<mix>.json`, a per-layer metric `<metric>` is read by
`bench/metrics/<metric>.py`, and an MRF observation `<image>` is drawn by
`bench/images/<image>.py`.  Adding a cell, mix, metric or MRF energy adds
files and entries; nothing here changes.

A configuration file names its `kind` (`bn` or `mrf`), its `reference`
module (`bench/reference/<reference>.py`) and its `models`.  An MRF model
entry is the energy:

  * `height`, `width` and `labels` are the grid's shape;
  * `image` names the observation module (default `label_noise`), whose
    `draw(height, width, labels, traffic, seed)` returns an int32 (H, W)
    observation;
  * every other key is a field of the program's `GridMRF`, passed by name
    (`theta`, `h`, `data_cost`); a field the program lacks fails set-up
    with the program's own error, before anything is compiled;
  * the configuration's reference module takes the entry whole,
    `MRFReference(entry, spec)`, and refuses with `ValueError` any key it
    does not implement.

So a new MRF energy is a configuration file, a reference module and, if
its observations are new, an observation module."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list[dict]
    per_layer: list[dict]


def load_benchmark(root: pathlib.Path) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: pathlib.Path, name: str) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)],
    )


def _module(directory: pathlib.Path, name: str):
    path = directory / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench.{directory.name}.{name.replace('-', '_').replace('.', '_')}",
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metric_reader(name: str, directory: pathlib.Path = BENCH / "metrics"):
    """The `read(ctx)` function of a per-layer metric's own module."""
    return _module(directory, name).read


def image_drawer(name: str):
    """The `draw(height, width, labels, traffic, seed)` function of an MRF
    observation's own module."""
    return _module(BENCH / "images", name).draw
