"""Finding a cell's files by name: BENCHMARK.json at the checkout's root,
then bench/configs, bench/traffic, bench/families, bench/reference and
bench/metrics."""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def load_module(path: Path):
    """Import a file by path, under a name made from the path (registered,
    as an import would be, so that dataclasses can read its namespace)."""
    path = Path(path)
    name = "bench_dyn_" + "_".join(path.with_suffix("").parts[-3:]) \
        .replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def family_module(root: Path, spec: dict):
    """The module of the configuration file's model family,
    bench/families/<family>.py; a file that names none is "dense"."""
    return load_module(Path(root) / "bench" / "families"
                       / f"{spec.get('family', 'dense')}.py")


def benchmark(root: Path = ROOT) -> dict:
    with open(Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def cell(bench: dict, workload: str):
    """(cell, configuration entry, end-to-end metrics, per-layer metrics)
    of one workload name; an unknown name is an error."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"have {sorted(cells)}")
    w = cells[workload]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    layer = [m for m in bench["per_layer"] if applies(m)]
    return w, cfg, e2e, layer
