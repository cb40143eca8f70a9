"""``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found here by the name in
``BENCHMARK.json`` under any directory of ``paths``:

    <path>/configs/<config>.json     sizes, job, guarantees
    <path>/traffic/<traffic>.json    loop kind, in-flight count or rate
    <path>/layers/<metric>.py        reader: ``read(obs) -> float | None``

A later PR adds a cell, a configuration or a per-layer metric by adding
files and entries; it edits nothing that is here.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
LAST_LINE_KEYS = ("correct", "attempted", "failed", "metrics", "device")


class ManifestError(Exception):
    pass


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Manifest:
    def __init__(self, root: str | None = None) -> None:
        self.root = root or repo_root()
        path = os.path.join(self.root, "BENCHMARK.json")
        try:
            with open(path, encoding="utf-8") as fh:
                self.doc = json.load(fh)
        except OSError as exc:
            raise ManifestError(f"cannot read {path}: {exc}") from exc
        self.paths = list(self.doc["paths"])

    # -- lookups -------------------------------------------------------

    def _find(self, kind: str, filename: str) -> str:
        for p in self.paths:
            candidate = os.path.join(self.root, p, kind, filename)
            if os.path.isfile(candidate):
                return candidate
        raise ManifestError(
            f"no {kind}/{filename} under any of {self.paths}"
        )

    def workload(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise ManifestError(f"no workload named {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                with open(
                    os.path.join(self.root, c["file"]), encoding="utf-8"
                ) as fh:
                    return json.load(fh)
        # a configuration no cell of BENCHMARK.json uses yet (the tests'
        # small fleets, a cell that PERF.md keeps for later)
        with open(self._find("configs", name + ".json"), encoding="utf-8") as fh:
            return json.load(fh)

    def traffic(self, name: str) -> dict:
        with open(self._find("traffic", name + ".json"), encoding="utf-8") as fh:
            return json.load(fh)

    def metrics_of(self, section: str, workload: str) -> list:
        """The ``end_to_end`` or ``per_layer`` metrics a cell reports:
        those that list it, and those that list no cells at all."""
        out = []
        for m in self.doc[section]:
            cells = m.get("workloads")
            if cells is None or workload in cells:
                out.append(m)
        return out

    def layer_reader(self, metric: str):
        """The ``read(obs)`` function of a per-layer metric's file."""
        path = self._find("layers", metric + ".py")
        spec = importlib.util.spec_from_file_location(
            "benchmark_layer_" + re.sub(r"\W", "_", metric), path
        )
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.read

    # -- schema --------------------------------------------------------

    def check(self) -> None:
        """The parts of the contract a file can be held to offline."""
        doc = self.doc
        want = {
            "command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer",
        }
        if set(doc) != want:
            raise ManifestError(f"keys {sorted(doc)} != {sorted(want)}")
        if not (1 <= int(doc["run_seconds"]) <= 51):
            raise ManifestError("run_seconds outside 1..51")
        names: dict = {}

        def name(kind: str, value: str) -> None:
            if not NAME_RE.match(value):
                raise ManifestError(f"bad {kind} name {value!r}")
            if value in names.setdefault(kind, set()):
                raise ManifestError(f"duplicate {kind} name {value!r}")
            names[kind].add(value)

        for c in doc["configs"]:
            if set(c) != {"name", "source", "file", "reduced", "why"}:
                raise ManifestError(f"config keys: {sorted(c)}")
            name("config", c["name"])
            if not any(
                c["file"].startswith(p.rstrip("/") + "/") for p in self.paths
            ):
                raise ManifestError(f"{c['file']} is outside paths")
            self.config(c["name"])
        pairs = set()
        for w in doc["workloads"]:
            if set(w) != {"name", "config", "traffic", "chips", "why"}:
                raise ManifestError(f"workload keys: {sorted(w)}")
            name("workload", w["name"])
            if w["config"] not in names["config"]:
                raise ManifestError(f"{w['name']}: unknown config")
            if not NAME_RE.match(w["traffic"]):
                raise ManifestError(f"bad traffic name {w['traffic']!r}")
            if (w["config"], w["traffic"]) in pairs:
                raise ManifestError("a config/traffic pair appears twice")
            pairs.add((w["config"], w["traffic"]))
            if w["chips"] not in (1, 4):
                raise ManifestError("chips must be 1 or 4")
            if not (1 <= len(w["why"]) <= 200) or "\n" in w["why"]:
                raise ManifestError(f"{w['name']}: why is 1..200 on one line")
            self.traffic(w["traffic"])
        cells = names["workload"]
        for section in ("end_to_end", "per_layer"):
            for m in doc[section]:
                base = {"name", "unit", "better", "source"}
                base |= (
                    {"bound"} if section == "end_to_end" else {"layer", "moves"}
                )
                if set(m) - {"workloads"} != base:
                    raise ManifestError(f"{m.get('name')}: keys {sorted(m)}")
                name("metric", m["name"])
                if not UNIT_RE.match(m["unit"]):
                    raise ManifestError(f"{m['name']}: bad unit {m['unit']!r}")
                if m["better"] not in ("lower", "higher"):
                    raise ManifestError(f"{m['name']}: better")
                if m["source"] not in SOURCES:
                    raise ManifestError(f"{m['name']}: source")
                for cell in m.get("workloads", ()):
                    if cell not in cells:
                        raise ManifestError(f"{m['name']}: unknown cell {cell}")
        e2e = {m["name"]: m for m in doc["end_to_end"]}
        if "setup_s" not in e2e:
            raise ManifestError("setup_s is missing")
        for m in doc["end_to_end"]:
            if m["source"] not in ("host_clock", "device_trace"):
                raise ManifestError(f"{m['name']}: end-to-end source")
            if not (0 < float(m["bound"]) <= 0.25):
                raise ManifestError(f"{m['name']}: bound")
        for m in doc["per_layer"]:
            if m["moves"] not in e2e:
                raise ManifestError(f"{m['name']}: moves {m['moves']!r}")
            moved = e2e[m["moves"]].get("workloads")
            for cell in m.get("workloads", ()):
                if moved is not None and cell not in moved:
                    raise ManifestError(
                        f"{m['name']}: {cell} does not report {m['moves']}"
                    )
            self._find("layers", m["name"] + ".py")
        for w in doc["workloads"]:
            if len(self.metrics_of("end_to_end", w["name"])) < 2:
                raise ManifestError(f"{w['name']}: needs setup_s and one more")
            if not self.metrics_of("per_layer", w["name"]):
                raise ManifestError(f"{w['name']}: needs a per-layer metric")


def check_last_line(line: str, manifest: Manifest, workload: str, trace: bool):
    """The last line of a run, held to the contract's keys."""
    obj = json.loads(line)
    for key in LAST_LINE_KEYS:
        if key not in obj:
            raise ManifestError(f"last line lacks {key!r}")
    section = "per_layer" if trace else "end_to_end"
    allowed = {m["name"]: m for m in manifest.metrics_of(section, workload)}
    for mname, entry in obj["metrics"].items():
        if mname not in allowed:
            raise ManifestError(f"metric {mname!r} is not this cell's")
        if entry["unit"] != allowed[mname]["unit"]:
            raise ManifestError(f"{mname}: unit {entry['unit']!r}")
        if not isinstance(entry["value"], (int, float)):
            raise ManifestError(f"{mname}: value")
    if not trace and set(obj["metrics"]) != set(allowed):
        raise ManifestError("an end-to-end metric of the cell is missing")
    dev = obj["device"]
    for key in ("platform", "kind", "count", "memory_peak_bytes"):
        if key not in dev:
            raise ManifestError(f"device lacks {key!r}")
    if trace:
        for key in ("busy_s", "window_s"):
            if key not in dev:
                raise ManifestError(f"device lacks {key!r}")
    return obj
