"""Find a cell's files by the names in ``BENCHMARK.json``. Imports no jax.

A cell is ``workloads/<cell>.json``; it names its configuration
(``configs/<config>.json``, which names its ``family``, the module under
``families/`` that knows the program's names for that kind of model), its
``kind`` (the driver under ``drivers/``)
and its traffic mix (``traffic/<mix>.json``, a file of parameters that
names the general generator, a module under ``traffic/``, that reads it). Per-layer metrics
are the files under ``metrics/`` whose ``cells`` or ``kinds`` include the
cell; each names a reader under ``readers/``. A later PR adds files and
manifest entries and edits nothing here.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(BENCH_DIR)
# keys of every configuration file that say where it comes from and how it
# is run, and describe no shape: a family refuses any other key it does
# not understand
BOOKKEEPING_KEYS = ("name", "source", "family", "model_type", "reduced",
                    "changed_from_source", "assumed", "program",
                    "deployment", "notes")


def export_environment(*, rehearsal: bool) -> None:
    """What the cluster's processes inherit: workers and replicas import
    ``benchmark.*`` and ``ray_tpu`` by name, and a rehearsal stays on the
    CPU whatever the machine holds."""
    import sys

    if REPO_ROOT not in sys.path:
        sys.path.insert(0, REPO_ROOT)
    others = [p for p in os.environ.get("PYTHONPATH", "").split(os.pathsep)
              if p and p != REPO_ROOT]
    os.environ["PYTHONPATH"] = os.pathsep.join([REPO_ROOT] + others)
    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.pop("XLA_FLAGS", None)


class BenchmarkFileError(Exception):
    """A file the benchmark needs is missing or disagrees with another."""


def _read_json(path: str) -> Any:
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except FileNotFoundError:
        raise BenchmarkFileError(f"missing file {path}") from None
    except json.JSONDecodeError as e:
        raise BenchmarkFileError(f"{path} is not JSON: {e}") from None


def load_manifest(repo_root: str = REPO_ROOT) -> Dict[str, Any]:
    return _read_json(os.path.join(repo_root, "BENCHMARK.json"))


def _merge(base: Dict, over: Dict) -> Dict:
    out = dict(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = _merge(out[k], v)
        else:
            out[k] = v
    return out


def load_cell(name: str, *, bench_dir: str = BENCH_DIR,
              rehearsal: bool = False) -> Dict[str, Any]:
    """The cell's file, with its configuration's file under ``model``.
    ``rehearsal`` lays ``<cell>.rehearsal.json`` over both (tiny sizes)."""
    cell = _read_json(os.path.join(bench_dir, "workloads", name + ".json"))
    if cell.get("name") != name:
        raise BenchmarkFileError(
            f"workloads/{name}.json names itself {cell.get('name')!r}")
    model = load_config(cell["config"], bench_dir=bench_dir)
    mix_name = cell["traffic"]
    mix = _read_json(os.path.join(bench_dir, "traffic", mix_name + ".json"))
    if mix.get("name") != mix_name:
        raise BenchmarkFileError(
            f"traffic/{mix_name}.json names itself {mix.get('name')!r}")
    cell["traffic"] = mix
    if rehearsal:
        over = _read_json(os.path.join(
            bench_dir, "workloads", name + ".rehearsal.json"))
        model = _merge(model, over.get("model", {}))
        cell = _merge(cell, {k: v for k, v in over.items() if k != "model"})
    load_family(model, bench_dir=bench_dir).check(model)
    cell["model"] = model
    return cell


def load_config(name: str, *, bench_dir: str = BENCH_DIR) -> Dict[str, Any]:
    cfg = _read_json(os.path.join(bench_dir, "configs", name + ".json"))
    if cfg.get("name") != name:
        raise BenchmarkFileError(
            f"configs/{name}.json names itself {cfg.get('name')!r}")
    return cfg


def load_metric_files(*, bench_dir: str = BENCH_DIR) -> List[Dict[str, Any]]:
    mdir = os.path.join(bench_dir, "metrics")
    out = []
    for fn in sorted(os.listdir(mdir)):
        if fn.endswith(".json"):
            m = _read_json(os.path.join(mdir, fn))
            if m.get("name") + ".json" != fn:
                raise BenchmarkFileError(
                    f"metrics/{fn} names itself {m.get('name')!r}")
            out.append(m)
    return out


def metric_applies(metric: Dict[str, Any], cell: Dict[str, Any]) -> bool:
    return (cell["name"] in metric.get("cells", ())
            or cell["kind"] in metric.get("kinds", ()))


def metrics_for_cell(cell: Dict[str, Any], *,
                     bench_dir: str = BENCH_DIR) -> List[Dict[str, Any]]:
    return [m for m in load_metric_files(bench_dir=bench_dir)
            if metric_applies(m, cell)]


def _load_module(bench_dir: str, sub: str, name: str):
    """``benchmark.<sub>.<name>`` when ``bench_dir`` is this package (so
    worker processes import the same module by name), else by path."""
    if os.path.abspath(bench_dir) == BENCH_DIR:
        return importlib.import_module(f"benchmark.{sub}.{name}")
    path = os.path.join(bench_dir, sub, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"_bench_{sub}_{name}", path)
    if spec is None or not os.path.exists(path):
        raise BenchmarkFileError(f"missing file {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: Dict[str, Any], *, bench_dir: str = BENCH_DIR):
    """``"reader": "<module>:<function>"`` under ``readers/``."""
    mod_name, _, fn_name = metric["reader"].partition(":")
    mod = _load_module(bench_dir, "readers", mod_name)
    return getattr(mod, fn_name or "read")


def load_traffic(cell: Dict[str, Any], *, bench_dir: str = BENCH_DIR):
    return _load_module(bench_dir, "traffic", cell["traffic"]["generator"])


def load_driver(cell: Dict[str, Any], *, bench_dir: str = BENCH_DIR):
    return _load_module(bench_dir, "drivers", cell["kind"])


def load_family(model: Dict[str, Any], *, bench_dir: str = BENCH_DIR):
    """``families/<family>.py``, named by the configuration file: what the
    drivers and readers take from the program and the reference for this
    kind of model (``families/dense_decoder.py`` says what a family gives)."""
    if "family" not in model:
        raise BenchmarkFileError(
            f"configs/{model.get('name')}.json names no family")
    return _load_module(bench_dir, "families", model["family"])


def load_reference(model: Dict[str, Any], *, bench_dir: str = BENCH_DIR):
    """``reference/<name>.py``, named by the configuration's family: the
    plain implementation whose ``loss`` and ``last_logits`` decide
    ``correct``. It imports jax: worker and replica only."""
    family = load_family(model, bench_dir=bench_dir)
    return _load_module(bench_dir, "reference", family.REFERENCE)


def manifest_metrics(manifest: Dict[str, Any], cell_name: str,
                     group: str) -> List[Dict[str, Any]]:
    """The manifest's metrics of ``group`` that this cell reports."""
    return [m for m in manifest[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def manifest_cell(manifest: Dict[str, Any],
                  cell_name: str) -> Optional[Dict[str, Any]]:
    for w in manifest["workloads"]:
        if w["name"] == cell_name:
            return w
    return None
