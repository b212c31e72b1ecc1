"""``BENCHMARK.json`` and the files it names.

The manifest is data: a cell names a configuration and a traffic mix,
the traffic file names the driver, a per-layer metric names its reader.
Each is looked up by that name under ``root`` — adding a cell, a metric
or a kind of traffic adds files and entries and edits none.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
import sys

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


class ManifestError(ValueError):
    """``BENCHMARK.json`` or a file it names breaks the contract."""


def _name(value, what: str) -> str:
    if not isinstance(value, str) or not NAME_RE.match(value):
        raise ManifestError(f"{what} {value!r} is not a name (letters, "
                            f"digits, '_', '.', '-'; at most 64)")
    return value


def _metric(entry: dict, *, end_to_end: bool) -> dict:
    _name(entry.get("name"), "metric name")
    if not UNIT_RE.match(str(entry.get("unit", ""))):
        raise ManifestError(f"metric {entry['name']}: bad unit "
                            f"{entry.get('unit')!r}")
    if entry.get("better") not in ("lower", "higher"):
        raise ManifestError(f"metric {entry['name']}: better must be "
                            f"'lower' or 'higher'")
    allowed = ("host_clock", "device_trace") if end_to_end else SOURCES
    if entry.get("source") not in allowed:
        raise ManifestError(f"metric {entry['name']}: source "
                            f"{entry.get('source')!r} not in {allowed}")
    keys = {"name", "unit", "better", "source", "workloads"}
    keys |= {"bound"} if end_to_end else {"layer", "moves"}
    extra = set(entry) - keys
    if extra:
        raise ManifestError(f"metric {entry['name']}: unknown keys "
                            f"{sorted(extra)}")
    return entry


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with the files it names, loaded."""

    name: str
    chips: int
    config: dict        #: the configuration file's contents
    traffic: dict       #: the traffic file's contents
    end_to_end: tuple   #: names of the end-to-end metrics this cell reports
    per_layer: tuple    #: names of the per-layer metrics this cell reports


class Manifest:
    """The validated manifest under ``root`` (default: the checkout)."""

    def __init__(self, root: str | None = None):
        self.root = root or os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))
        path = os.path.join(self.root, "BENCHMARK.json")
        with open(path) as f:
            self.doc = json.load(f)
        self._validate()

    def _validate(self) -> None:
        d = self.doc
        if set(d) != TOP_KEYS:
            raise ManifestError(f"BENCHMARK.json keys {sorted(d)} != "
                                f"{sorted(TOP_KEYS)}")
        if not isinstance(d["run_seconds"], int) \
                or not 1 <= d["run_seconds"] <= 51:
            raise ManifestError("run_seconds must be a whole number 1..51")
        names: set[str] = set()
        for group, e2e in (("end_to_end", True), ("per_layer", False)):
            for m in d[group]:
                _metric(m, end_to_end=e2e)
                if m["name"] in names:
                    raise ManifestError(f"metric {m['name']} named twice")
                names.add(m["name"])
        e2e_names = {m["name"] for m in d["end_to_end"]}
        if "setup_s" not in e2e_names:
            raise ManifestError("end_to_end must hold setup_s")
        for m in d["per_layer"]:
            if m["moves"] not in e2e_names:
                raise ManifestError(f"{m['name']} moves {m['moves']!r}, "
                                    f"which is no end-to-end metric")
        cfgs = {}
        for c in d["configs"]:
            _name(c.get("name"), "config name")
            for key in c.get("reduced", []):
                _name(key, f"config {c['name']}: reduced key")
            if not any(c["file"].startswith(p.rstrip("/") + "/")
                       for p in d["paths"]):
                raise ManifestError(f"config {c['name']}: file "
                                    f"{c['file']!r} is outside paths")
            cfgs[c["name"]] = c
        if len(cfgs) != len(d["configs"]):
            raise ManifestError("two configs share a name")
        seen = set()
        for w in d["workloads"]:
            _name(w.get("name"), "workload name")
            _name(w.get("traffic"), f"workload {w['name']}: traffic")
            if w.get("config") not in cfgs:
                raise ManifestError(f"workload {w['name']}: unknown "
                                    f"config {w.get('config')!r}")
            if w.get("chips") not in (1, 4):
                raise ManifestError(f"workload {w['name']}: chips must "
                                    f"be 1 or 4")
            if len(str(w.get("why", ""))) not in range(1, 201):
                raise ManifestError(f"workload {w['name']}: why must "
                                    f"have 1 to 200 characters")
            if w["name"] in seen:
                raise ManifestError(f"workload {w['name']} named twice")
            seen.add(w["name"])
        for m in d["end_to_end"] + d["per_layer"]:
            for wl in m.get("workloads", []):
                if wl not in seen:
                    raise ManifestError(f"metric {m['name']} lists "
                                        f"unknown workload {wl!r}")

    # -- lookups -----------------------------------------------------------

    def workload_names(self) -> list[str]:
        return [w["name"] for w in self.doc["workloads"]]

    def metric(self, name: str) -> dict:
        for m in self.doc["end_to_end"] + self.doc["per_layer"]:
            if m["name"] == name:
                return m
        raise KeyError(name)

    def _metrics_of(self, group: str, workload: str) -> tuple:
        return tuple(m["name"] for m in self.doc[group]
                     if "workloads" not in m or workload in m["workloads"])

    def _load_json(self, rel: str) -> dict:
        with open(os.path.join(self.root, rel)) as f:
            return json.load(f)

    def cell(self, workload: str) -> Cell:
        for w in self.doc["workloads"]:
            if w["name"] == workload:
                break
        else:
            raise ManifestError(f"no workload {workload!r}; there are "
                                f"{self.workload_names()}")
        cfg = next(c for c in self.doc["configs"] if c["name"] == w["config"])
        return Cell(
            name=w["name"], chips=w["chips"],
            config=self._load_json(cfg["file"]),
            traffic=self._load_json(
                os.path.join(self.doc["paths"][0], "traffic",
                             w["traffic"] + ".json")),
            end_to_end=self._metrics_of("end_to_end", workload),
            per_layer=self._metrics_of("per_layer", workload))

    def _module(self, kind: str, name: str):
        """The module ``<paths[0]>/<kind>/<name>.py``, loaded by path (so
        a file that a later PR adds is found without an import edit)."""
        _name(name, kind)
        path = os.path.join(self.root, self.doc["paths"][0], kind,
                            name + ".py")
        if not os.path.exists(path):
            raise ManifestError(f"{kind} {name!r}: no file {path}")
        modname = f"chipbench_{kind}_" + re.sub(r"\W", "_", name)
        spec = importlib.util.spec_from_file_location(modname, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[modname] = mod   # dataclasses look their module up
        spec.loader.exec_module(mod)
        return mod

    def driver(self, cell: Cell):
        return self._module("drivers", cell.traffic["driver"])

    def reader(self, metric_name: str):
        return self._module("metrics", metric_name)
