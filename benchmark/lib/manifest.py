"""BENCHMARK.json and the data files it names: load, check, resolve.

A cell, a configuration, a traffic mix or a per-layer metric is found by
its name and nothing else: `configs[].file` for a configuration,
`traffic/<mix>.json` for a mix, `layer_metrics/<metric>.json` for a
per-layer metric. Adding one is adding files and entries.
"""

import json
import os
import re

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    pass


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load(root):
    return load_json(os.path.join(root, "BENCHMARK.json"))


def bench_dir(manifest):
    """The directory under `paths` that holds run.py's data files."""
    return os.path.dirname(manifest["command"][1])


def problems(manifest):
    """Every breach of the contract's limits on names, units and references
    that can be seen without running anything; empty when sound."""
    out = []

    def name(what, value):
        if not isinstance(value, str) or not NAME_RE.match(value):
            out.append(f"{what}: {value!r} is not a name")

    configs = {c.get("name") for c in manifest.get("configs", [])}
    cells = {w.get("name") for w in manifest.get("workloads", [])}
    e2e = {m.get("name") for m in manifest.get("end_to_end", [])}
    for c in manifest.get("configs", []):
        name("config", c.get("name"))
        for key in c.get("reduced", []):
            name(f"config {c.get('name')} reduced", key)
        if not any(c.get("file", "").startswith(p.rstrip("/") + "/")
                   for p in manifest.get("paths", [])):
            out.append(f"config {c.get('name')}: file outside paths")
    for w in manifest.get("workloads", []):
        name("workload", w.get("name"))
        name("traffic", w.get("traffic"))
        if w.get("config") not in configs:
            out.append(f"workload {w.get('name')}: unknown config")
        if w.get("chips") not in (1, 4):
            out.append(f"workload {w.get('name')}: chips must be 1 or 4")
        if not 1 <= len(w.get("why", "")) <= 200:
            out.append(f"workload {w.get('name')}: why must be 1..200 chars")
    for kind in ("end_to_end", "per_layer"):
        for m in manifest.get(kind, []):
            name(kind, m.get("name"))
            if not UNIT_RE.match(str(m.get("unit", ""))):
                out.append(f"{kind} {m.get('name')}: bad unit {m.get('unit')!r}")
            if m.get("better") not in ("lower", "higher"):
                out.append(f"{kind} {m.get('name')}: better must be lower|higher")
            if m.get("source") not in SOURCES:
                out.append(f"{kind} {m.get('name')}: unknown source")
            for cell in m.get("workloads", []):
                if cell not in cells:
                    out.append(f"{kind} {m.get('name')}: unknown cell {cell}")
    for m in manifest.get("per_layer", []):
        if m.get("moves") not in e2e:
            out.append(f"per_layer {m.get('name')}: moves no end-to-end metric")
    for m in manifest.get("end_to_end", []):
        if not 0 < m.get("bound", 0) <= 0.25:
            out.append(f"end_to_end {m.get('name')}: bound outside (0, 0.25]")
    if "setup_s" not in e2e:
        out.append("end_to_end lacks setup_s")
    names = [m.get("name") for k in ("end_to_end", "per_layer")
             for m in manifest.get(k, [])]
    for kind, seq in (("metric", names), ("workload", [w.get("name") for w in
                      manifest.get("workloads", [])]),
                      ("config", [c.get("name") for c in
                                  manifest.get("configs", [])])):
        if len(seq) != len(set(seq)):
            out.append(f"duplicate {kind} name")
    return out


def _in_cell(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


class Cell:
    """One workload of the manifest with everything it names resolved."""

    def __init__(self, manifest, root, workload):
        found = [w for w in manifest["workloads"] if w["name"] == workload]
        if not found:
            raise ManifestError(
                f"no workload {workload!r}; BENCHMARK.json has "
                f"{[w['name'] for w in manifest['workloads']]}")
        self.entry = found[0]
        self.name = workload
        self.chips = self.entry["chips"]
        self.root = root
        base = os.path.join(root, bench_dir(manifest))
        cfg = [c for c in manifest["configs"]
               if c["name"] == self.entry["config"]]
        if not cfg:
            raise ManifestError(f"{workload}: no config {self.entry['config']!r}")
        self.config_name = cfg[0]["name"]
        self.config = load_json(os.path.join(root, cfg[0]["file"]))
        self.traffic_name = self.entry["traffic"]
        self.traffic = load_json(
            os.path.join(base, "traffic", self.traffic_name + ".json"))
        self.end_to_end = [m for m in manifest["end_to_end"]
                           if _in_cell(m, workload)]
        self.per_layer = []
        for m in manifest["per_layer"]:
            if _in_cell(m, workload):
                spec = load_json(os.path.join(base, "layer_metrics",
                                              m["name"] + ".json"))
                self.per_layer.append(dict(spec, name=m["name"],
                                           unit=m["unit"]))
        self.state_dir = os.path.join(base, ".state", workload)

        # the configurations the traffic draws jobs from: the cell's own,
        # unless the mix lists several by name with weights
        by_name = {c["name"]: c for c in manifest["configs"]}
        self.job_mix = []
        for part in self.traffic.get("configs") or [
                {"config": self.config_name, "weight": 1}]:
            if part["config"] not in by_name:
                raise ManifestError(
                    f"{workload}: traffic names no config {part['config']!r}")
            conf = (self.config if part["config"] == self.config_name else
                    load_json(os.path.join(root, by_name[part["config"]]["file"])))
            self.job_mix.append((conf["job"], part.get("weight", 1)))
