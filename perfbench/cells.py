"""Name -> file resolution: the only place that knows where things live.

``BENCHMARK.json`` (one directory above this package) names the cells, the
configurations and the metrics; everything that belongs to ONE of them is a
file of its own under this package, found by that name:

    workloads[].config   -> configs[].file               (sizes, as run)
    workloads[].traffic  -> traffic/<traffic>.json       (parameters of the mix)
    traffic["driver"]    -> drivers/<driver>.py          (kind of run)
    config["builder"]    -> builders/<builder>.py        (program under test)
                            reference/<builder>.py       (plain reference)
    per_layer[].name     -> layer_metrics/<name>.py      (one reader each)

Nothing here lists a cell, a metric or a model: adding one is adding files
and a ``BENCHMARK.json`` entry, never an edit to a file that exists.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
import sys
from pathlib import Path
from types import ModuleType

BENCH_DIR = Path(__file__).resolve().parent

#: the contract's plain name: starts with a letter or digit, at most 64 of
#: letters, digits, ``_``, ``.`` and ``-``
_PLAIN_NAME = re.compile(r"[A-Za-z0-9][A-Za-z0-9_.-]{0,63}")


class CellError(ValueError):
    """A name in ``BENCHMARK.json`` or on the command line resolves to
    nothing (unknown cell, missing file, malformed entry)."""


class DeviceError(RuntimeError):
    """The machine is not what the cell needs (no TPU, an unknown device
    kind, too few chips): a driver raises it, the run exits non-zero and
    prints no result."""


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""

    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    #: the ``end_to_end`` / ``per_layer`` entries that apply to this cell
    #: (an entry without a ``workloads`` key applies to every cell)
    end_to_end: tuple
    per_layer: tuple
    bench_dir: Path


def _plain(name: str, what: str) -> str:
    if not isinstance(name, str) or not _PLAIN_NAME.fullmatch(name):
        raise CellError(f"{what} {name!r} is not a plain name")
    return name


def _read_json(path: Path, what: str) -> dict:
    if not path.is_file():
        raise CellError(f"{what}: no file {path}")
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    if not isinstance(data, dict):
        raise CellError(f"{what}: {path} is not a JSON object")
    return data


def load_benchmark(bench_dir: Path = BENCH_DIR) -> dict:
    return _read_json(bench_dir.parent / "BENCHMARK.json", "benchmark")


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def resolve(workload: str, bench_dir: Path = BENCH_DIR,
            config_override: str | None = None) -> Cell:
    """The cell ``workload`` of ``BENCHMARK.json`` with its files loaded.

    ``config_override`` names a file ``configs/<name>.json`` to run in place
    of the cell's own configuration (the CPU rehearsal's tiny widths); the
    traffic, the driver and the metrics stay the cell's."""
    bench = load_benchmark(bench_dir)
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        known = ", ".join(w["name"] for w in bench["workloads"])
        raise CellError(f"unknown workload {workload!r} (known: {known})")
    config_name = _plain(entry["config"], "config")
    traffic_name = _plain(entry["traffic"], "traffic")
    if config_override is not None:
        config_path = bench_dir / "configs" / f"{config_override}.json"
    else:
        declared = next((c for c in bench["configs"]
                         if c["name"] == config_name), None)
        if declared is None:
            raise CellError(f"workload {workload!r} names config "
                            f"{config_name!r}, which `configs` does not list")
        config_path = bench_dir.parent / declared["file"]
    traffic = _read_json(bench_dir / "traffic" / f"{traffic_name}.json",
                         f"traffic {traffic_name!r}")
    if int(entry["chips"]) not in (1, 4):
        raise CellError(f"workload {workload!r}: chips must be 1 or 4")
    return Cell(
        name=workload,
        chips=int(entry["chips"]),
        config_name=config_name,
        traffic_name=traffic_name,
        config=_read_json(config_path, f"config {config_name!r}"),
        traffic=traffic,
        end_to_end=tuple(m for m in bench["end_to_end"]
                         if _applies(m, workload)),
        per_layer=tuple(m for m in bench["per_layer"]
                        if _applies(m, workload)),
        bench_dir=bench_dir,
    )


def load_plugin(kind: str, name: str, bench_dir: Path = BENCH_DIR) -> ModuleType:
    """Import ``<bench_dir>/<kind>/<name>.py`` (a driver, builder, reference
    or per-layer metric) by its name — there is no registry to edit."""
    path = bench_dir / _plain(kind, "plugin kind") / f"{_plain(name, kind)}.py"
    if not path.is_file():
        raise CellError(f"{kind} {name!r}: no file {path}")
    module_name = "perfbench_plugin_" + re.sub(r"\W", "_", f"{kind}_{name}")
    spec = importlib.util.spec_from_file_location(module_name, path)
    module = importlib.util.module_from_spec(spec)
    # registered before exec so dataclasses/pickling inside the plugin can
    # resolve their own module
    sys.modules[module_name] = module
    spec.loader.exec_module(module)
    return module
