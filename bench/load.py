"""Find a cell's files by the names ``BENCHMARK.json`` gives them.

* configuration ``<c>``: ``configs/<c>.json`` (its sizes, as run) and
  ``configs/<c>.py`` (weights from the seed, FLOP and byte counts, and the
  plain reference; it imports nothing of the program);
* traffic mix ``<t>``: ``traffic/<t>.json``, parameters; its ``kind``
  names the generator that reads them;
* traffic kind ``<k>``: ``kinds/<k>.py``, whose ``Generator`` builds the
  program's entries, warms them up, runs the window, and reads the
  numbers that decide ``correct`` and the work the window did;
* per-layer metric ``<m>``: ``metrics/<m>.py``, whose ``read(ctx)`` returns
  a number or ``None`` when the run holds nothing to read;
* cell ``<w>``: ``limits/<w>.json``, the limits of the numbers that decide
  ``correct``.

A later change adds a cell, a configuration or a metric by adding files
and entries; no file here names one.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parent
REPO = BENCH.parent


def benchmark(path: pathlib.Path = REPO / "BENCHMARK.json") -> dict:
    return json.loads(path.read_text())


def cell(bm: dict, name: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                   f"known: {[w['name'] for w in bm['workloads']]}")


def _json(root: pathlib.Path, kind: str, name: str) -> dict:
    path = root / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"{kind} {name!r}: no file {path}")
    return json.loads(path.read_text())


def _module(path: pathlib.Path):
    if not path.is_file():
        raise FileNotFoundError(f"no file {path}")
    mod_name = "bench_" + "_".join(path.relative_to(path.parents[1]).with_suffix("").parts)
    mod_name = "".join(ch if ch.isalnum() else "_" for ch in mod_name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def config(name: str, root: pathlib.Path = BENCH):
    """(sizes, module) of configuration ``name``."""
    return _json(root, "configs", name), _module(root / "configs" / f"{name}.py")


def traffic(name: str, root: pathlib.Path = BENCH) -> dict:
    return _json(root, "traffic", name)


def kind(name: str, root: pathlib.Path = BENCH):
    return _module(root / "kinds" / f"{name}.py")


def metric(name: str, root: pathlib.Path = BENCH):
    return _module(root / "metrics" / f"{name}.py")


def limits(cell_name: str, root: pathlib.Path = BENCH) -> dict:
    return _json(root, "limits", cell_name)


def cell_metrics(bm: dict, cell_name: str, group: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries a cell reports.

    An entry with a ``workloads`` key belongs to the cells it lists. A
    per-layer entry without one belongs to every cell that reports the
    end-to-end metric it ``moves``.
    """
    e2e = [m for m in bm["end_to_end"]
           if cell_name in m.get("workloads", [cell_name])]
    if group == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bm["per_layer"]
            if cell_name in m.get("workloads", [cell_name])
            and ("workloads" in m or m["moves"] in names)]
