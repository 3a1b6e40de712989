"""Finds a cell's files by the names `BENCHMARK.json` gives.

A cell is `<config>.<traffic>`. Its configuration is the file the config
entry names, its traffic mix `chipbench/traffic/<traffic>.json`, and each
per-layer metric `chipbench/layers/<metric>.json`, whose `reader` names a
module `chipbench/readers/<reader>.py`. A later PR adds a configuration,
a mix, a metric or a reader by adding files and entries; no file here
is edited for it.
"""

from __future__ import annotations

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(path: str) -> dict:
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def benchmark(root: str = ROOT) -> dict:
    return _load(os.path.join(root, "BENCHMARK.json"))


class Cell:
    """One workload of BENCHMARK.json with everything found by name."""

    def __init__(self, name: str, root: str = ROOT, bench: dict | None = None):
        bench = bench or benchmark(root)
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(
                f"unknown workload {name!r}; BENCHMARK.json has {sorted(cells)}"
            )
        self.name = name
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        cfg_entry = {c["name"]: c for c in bench["configs"]}[self.entry["config"]]
        self.config = _load(os.path.join(root, cfg_entry["file"]))
        self.traffic = _load(
            os.path.join(root, "chipbench", "traffic", self.entry["traffic"] + ".json")
        )
        self.end_to_end = [
            m for m in bench["end_to_end"]
            if "workloads" not in m or name in m["workloads"]
        ]
        e2e = {m["name"] for m in self.end_to_end}
        self.per_layer = []
        for m in bench["per_layer"]:
            if ("workloads" in m and name not in m["workloads"]) or m["moves"] not in e2e:
                continue
            self.per_layer.append(layer_metric(m["name"], root))

    def sized(self, tiny: bool) -> dict:
        """The configuration as run: its `tiny` overrides laid over it for
        a CPU rehearsal, untouched on the chip."""
        cfg = dict(self.config)
        if tiny:
            over = cfg.get("tiny", {})
            cfg.update({k: v for k, v in over.items() if k != "env"})
            cfg["env"] = {**cfg.get("env", {}), **over.get("env", {})}
        return cfg

    def deploy(self, tiny: bool) -> dict:
        """`sized`, with the deployment's knobs put into the environment
        the program reads them from."""
        if tiny and os.environ.get("JAX_PLATFORMS", "") != "cpu":
            raise SystemExit("--tiny is the CPU rehearsal: set JAX_PLATFORMS=cpu")
        cfg = self.sized(tiny)
        for key, value in cfg.get("env", {}).items():
            os.environ[key] = str(value)
        return cfg


def layer_metric(name: str, root: str = ROOT) -> dict:
    spec = _load(os.path.join(root, "chipbench", "layers", name + ".json"))
    if spec["name"] != name:
        raise SystemExit(f"chipbench/layers/{name}.json names {spec['name']!r}")
    return spec


def reader(name: str):
    """The `read(record, params)` of chipbench/readers/<name>.py."""
    return importlib.import_module(f"chipbench.readers.{name}").read


def read_layers(cell: Cell, record: dict) -> dict:
    """Every per-layer metric of the cell that finds something to read;
    a reader that finds nothing returns None and the metric is left out."""
    out = {}
    for spec in cell.per_layer:
        value = reader(spec["reader"])(record, spec.get("params", {}))
        if value is not None:
            out[spec["name"]] = {"value": float(value), "unit": spec["unit"]}
    return out


def device(tiny: bool) -> dict:
    """The compile cache on at its fixed place, and the device as JAX
    reports it: a TPU, or SystemExit, unless this is the CPU rehearsal."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from foremast_tpu.device import device_info, enable_compile_cache, require_tpu

    cache_dir = enable_compile_cache()
    info = device_info() if tiny else require_tpu()
    return {**info, "compile_cache": cache_dir}
