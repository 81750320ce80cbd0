"""Find a cell's configuration, traffic, metrics and peaks by name.

Everything that belongs to one deployment, one traffic mix or one
metric sits in a file of its own, found by the name ``BENCHMARK.json``
gives it:

* ``bench/configs/<config>.json`` -- the deployment (the entry's ``file``);
* ``bench/traffic/<traffic>.json`` -- the traffic mix's parameters;
* ``bench/metrics/<metric>.py`` -- the reader of one metric, a function
  ``read(run)`` that returns a number, or None where it finds nothing;
* ``bench/peaks.json`` -- the chip's peaks, keyed by ``device_kind``.

So a cell, a mix or a metric is added by adding files and entries.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Callable

import numpy as np

ROOT = Path(__file__).resolve().parents[2]  # the checkout: bench/yardstick/..


@dataclasses.dataclass(frozen=True)
class Deployment:
    """An erasure-coded deployment, as its configuration file states it."""

    name: str
    family: str
    n: int
    k: int
    r: int
    alpha: int
    block_bytes: int  # nominal block, as the source gives it
    sub_bytes: int  # one of the alpha sub-blocks, 128-lane aligned
    stripes: int  # resident stripes the failed node held
    failed: int
    chips: int
    mesh: tuple[int, int]
    helpers_read: int  # nodes whose blocks a repair must read
    cross_rack_blocks: float  # per stripe, as the plan's bound gives it
    parity: np.ndarray  # ((n-k)*alpha, k*alpha) generator rows below I

    @classmethod
    def from_dict(cls, cfg: dict[str, Any]) -> "Deployment":
        code = cfg["code"]
        parity = np.array([list(bytes.fromhex(h))
                           for h in cfg["generator_parity_rows_hex"]], np.uint8)
        dep = cls(
            name=cfg["name"], family=code["family"], n=code["n"], k=code["k"],
            r=code["r"], alpha=code["alpha"], block_bytes=cfg["block_bytes"],
            sub_bytes=cfg["sub_bytes"], stripes=cfg["stripes"],
            failed=cfg["failed_node"], chips=cfg["layout"]["chips"],
            mesh=tuple(cfg["layout"]["mesh"]), helpers_read=cfg["helpers_read"],
            cross_rack_blocks=cfg["cross_rack_blocks_per_stripe"], parity=parity)
        want = ((dep.n - dep.k) * dep.alpha, dep.k * dep.alpha)
        if parity.shape != want:
            raise ValueError(f"{dep.name}: parity rows {parity.shape} != {want}")
        if dep.sub_bytes % 128 or dep.sub_bytes * dep.alpha < dep.block_bytes:
            raise ValueError(f"{dep.name}: sub-block {dep.sub_bytes} B does not "
                             f"hold a {dep.block_bytes} B block in 128-lane rows")
        return dep

    @property
    def generator(self) -> np.ndarray:
        """(n*alpha, k*alpha): systematic rows, then the parity rows."""
        ka = self.k * self.alpha
        return np.concatenate([np.eye(ka, dtype=np.uint8), self.parity])

    @property
    def failed_rows(self) -> np.ndarray:
        a = self.alpha
        return self.generator[self.failed * a:(self.failed + 1) * a]

    def least_hbm_bytes(self, width: int) -> int:
        """Least HBM bytes of one stripe's repair at ``width`` bytes per
        sub-block: the helpers' blocks read once, the rebuilt one written."""
        return (self.helpers_read + 1) * self.alpha * width


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of ``workloads``: a deployment under a traffic mix."""

    name: str
    chips: int
    deployment: Deployment
    traffic: dict[str, Any]
    end_to_end: tuple[dict[str, Any], ...]
    per_layer: tuple[dict[str, Any], ...]


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def reports(metric: dict[str, Any], workload: str) -> bool:
    """Whether ``workload`` reports ``metric``: it is listed, or the metric
    lists no cells."""
    return workload in metric.get("workloads", [workload])


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json: "
                       f"{sorted(cells)}")
    entry = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    dep = Deployment.from_dict(load_json(root / configs[entry["config"]]["file"]))
    if dep.chips != entry["chips"]:
        raise ValueError(f"{workload}: asks for {entry['chips']} chips, its "
                         f"configuration is laid out on {dep.chips}")
    traffic = load_json(root / "bench" / "traffic" / f"{entry['traffic']}.json")
    return Cell(
        name=workload, chips=entry["chips"], deployment=dep, traffic=traffic,
        end_to_end=tuple(m for m in bench["end_to_end"] if reports(m, workload)),
        per_layer=tuple(m for m in bench["per_layer"] if reports(m, workload)))


def load_reader(name: str, root: Path = ROOT) -> Callable[[Any], float | None]:
    """The ``read(run)`` function of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def load_peaks(device_kind: str, root: Path = ROOT) -> dict[str, Any]:
    """The peaks of ``device_kind``; a device not in the table is an error."""
    table = load_json(root / "bench" / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json: {sorted(table)}")
    return table[device_kind]
