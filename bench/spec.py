"""Finds every part of a cell by the name ``BENCHMARK.json`` gives it.

Layout under ``bench/``:

* ``configs/<file>``: a configuration (named by its ``file`` entry);
* ``traffic/<traffic>.json``: a traffic mix, read by ``bench/traffic.py``;
* ``metrics/<metric>.py``: one reader per metric, ``read(run) -> value``;
* ``reference/<name>.py``: a plain reference, named by a configuration;
* ``work/<name>.py``: the operations and bytes of the same block
  (``of(sizes)``), found by the reference's name.

A later cell, configuration, traffic mix, metric or block is a new file here
and a new entry in ``BENCHMARK.json``; nothing that exists needs an edit.
"""

from __future__ import annotations

import importlib.util
import json
import re
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class SpecError(ValueError):
    """A name or file the benchmark cannot resolve."""


def _checked(name: str) -> str:
    if not isinstance(name, str) or not _NAME.match(name):
        raise SpecError(f"not a benchmark name: {name!r}")
    return name


def benchmark(root: Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == _checked(name):
            return w
    raise SpecError(f"no workload named {name!r}")


def configuration(bench: dict, name: str, root: Path = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == _checked(name):
            return json.loads((root / c["file"]).read_text())
    raise SpecError(f"no configuration named {name!r}")


def traffic(name: str) -> dict:
    path = HERE / "traffic" / f"{_checked(name)}.json"
    if not path.is_file():
        raise SpecError(f"no traffic file {path.name}")
    return json.loads(path.read_text())


def _module(path: Path, label: str):
    if not path.is_file():
        raise SpecError(f"no file {path.relative_to(ROOT)} for {label}")
    mod_name = "bench_" + re.sub(r"\W", "_", path.stem) + "_" + path.parent.name
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod  # a dataclass looks its module up by name
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str):
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    return _module(HERE / "metrics" / f"{_checked(metric)}.py",
                   f"metric {metric}").read


def reference(name: str):
    """The module ``reference/<name>.py`` (a plain reference)."""
    return _module(HERE / "reference" / f"{_checked(name)}.py",
                   f"reference {name}")


def work(name: str):
    """The module ``work/<name>.py``: the counts of reference ``name``'s
    block."""
    return _module(HERE / "work" / f"{_checked(name)}.py", f"work {name}")


def metrics_for(bench: dict, cell_name: str, section: str) -> list:
    """Metric entries of ``section`` (``end_to_end`` or ``per_layer``) that
    the cell reports.  A metric without a ``workloads`` list is reported in
    every cell (end to end) or in every cell that reports the end-to-end
    metric it moves (per layer)."""
    e2e = {m["name"] for m in bench["end_to_end"]
           if "workloads" not in m or cell_name in m["workloads"]}
    out = []
    for m in bench[section]:
        if "workloads" in m:
            if cell_name in m["workloads"]:
                out.append(m)
        elif section == "end_to_end" or m["moves"] in e2e:
            out.append(m)
    return out

