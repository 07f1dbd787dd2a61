"""Find a cell's configuration, model family, traffic mix and metrics by
name.

Everything that belongs to one configuration, one model family, one mix
or one per-layer metric sits in a file of its own, so a later cell adds
files and edits none: ``configs/<config>.json``, which names its family
under ``architecture.family``; ``families/<family>.py``, the family's
weights, reference and work counts (``bench.families``);
``traffic/<mix>.json`` (which may ``extend`` another mix and override
some of its keys) and ``metrics/<metric>.py``.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
FAMILIES_DIR = BENCH_DIR / "families"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def merge(base: dict, over: dict) -> dict:
    """``base`` with ``over``'s keys laid on top, nested dicts merged."""
    out = dict(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = merge(out[k], v)
        else:
            out[k] = v
    return out


def load_mix(name: str, traffic_dir: Path = BENCH_DIR / "traffic") -> dict:
    """A traffic mix, with the mixes it extends resolved."""
    mix = load_json(traffic_dir / f"{name}.json")
    parent = mix.pop("extends", None)
    if parent is not None:
        mix = merge(load_mix(parent, traffic_dir), mix)
    mix["name"] = name
    return mix


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # bench/configs/<config>.json, as run
    mix: dict             # bench/traffic/<mix>.json, extends resolved
    end_to_end: list      # BENCHMARK.json metric entries this cell reports
    per_layer: list


def _reports(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def load_cell(name: str, bench_json: Path = ROOT / "BENCHMARK.json") -> Cell:
    bench = load_json(bench_json)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = load_json(ROOT / conf["file"])
    family_path(config)
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _reports(m, name, names)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                mix=load_mix(w["traffic"]), end_to_end=e2e,
                per_layer=per_layer)


def load_reader(metric: str, metrics_dir: Path = BENCH_DIR / "metrics"):
    """The ``read(record)`` function of ``metrics/<metric>.py``."""
    path = metrics_dir / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench.metrics.{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def family_path(config: dict, families_dir: Path = FAMILIES_DIR) -> Path:
    """The module of the family that ``config`` names; an error that names
    the file looked for where it names none or an unknown one."""
    family = config.get("architecture", {}).get("family")
    if family is None:
        raise ValueError(f"configuration {config.get('name')!r} names no "
                         f"architecture.family: the module "
                         f"{families_dir}/<family>.py it would load")
    path = families_dir / f"{family}.py"
    if not path.is_file():
        raise FileNotFoundError(f"configuration {config.get('name')!r} "
                                f"names family {family!r}, and there is no "
                                f"{path}")
    return path


def load_family(config: dict, families_dir: Path = FAMILIES_DIR):
    """The module ``families/<family>.py`` of the family that ``config``
    names, imported once per file."""
    path = family_path(config, families_dir)
    name = f"bench.families.{path.stem}"
    mod = sys.modules.get(name)
    if mod is not None and Path(mod.__file__).resolve() == path.resolve():
        return mod
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod
