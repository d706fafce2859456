"""Loads ``BENCHMARK.json`` and the data files it names, and checks them.

The harness is driven by data: a configuration is ``configs/<config>.json``
(the path ``BENCHMARK.json`` gives) naming its block module
``models/<module>.py``, a traffic mix ``traffic/<traffic>.json``, a per-layer
metric ``metrics/<metric>.json`` (a reader of ``readers.py`` or of a file under
``reader_files/``, with its arguments), and a cell one ``workloads`` entry
naming a configuration and a traffic mix. Adding any of them is adding files
and entries; nothing here is edited.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
# EngineConfig sizes a configuration file states at its top level.
ENGINE_SIZES = ("max_batch_size", "max_pages_per_seq", "max_decode_len", "warmup_max_len")
# What a configuration file says to the harness. Every OTHER top-level key is
# the model's own, and the configuration's block module has to account for it.
HARNESS_KEYS = frozenset(ENGINE_SIZES) | {
    "name", "source", "module", "chips", "mesh", "slab_rows", "mcpx",
    "reduced", "assumed", "departures", "params",
}
# What a block module gives (``load_block``); ``step_functions`` is optional.
BLOCK_PARTS = ("model_config", "rehearsal_config", "reference_logits", "kernel_paths")


class SpecError(ValueError):
    """``BENCHMARK.json`` or a data file breaks the benchmark's contract."""


def check_name(name: object, what: str) -> str:
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise SpecError(
            f"{what} {name!r}: a name is 1-64 of [A-Za-z0-9_.-] and does not "
            "start with '.' or '-'"
        )
    return name


def check_unit(unit: object, what: str) -> str:
    if not isinstance(unit, str) or not UNIT_RE.match(unit):
        raise SpecError(f"{what}: unit {unit!r} is not 1-16 of [A-Za-z0-9_/%.-]")
    return unit


def _read_json(path: str) -> dict:
    try:
        with open(path) as f:
            obj = json.load(f)
    except OSError as e:
        raise SpecError(f"cannot read {path}: {e}") from e
    except json.JSONDecodeError as e:
        raise SpecError(f"{path} is not JSON: {e}") from e
    if not isinstance(obj, dict):
        raise SpecError(f"{path} must hold one JSON object")
    return obj


def model_keys(config: dict) -> dict:
    """The configuration file without the harness's own keys: the published
    keys its block module maps to the program's model config."""
    return {k: v for k, v in config.items() if k not in HARNESS_KEYS}


def block_file(name: object, bench_dir: str = HERE) -> str:
    """``models/<name>.py`` of a configuration's ``"module": "<name>"``: a
    name, never a path or a dotted import."""
    if name is None:
        raise SpecError("the configuration names no 'module' (models/<module>.py); there is no default")
    path = os.path.join(bench_dir, "models", check_name(name, "config module") + ".py")
    if not os.path.isfile(path):
        models = os.path.dirname(path)
        have = sorted(f[:-3] for f in os.listdir(models) if f.endswith(".py")) if os.path.isdir(models) else []
        raise SpecError(f"config module {name!r}: no {path} (has: {have})")
    return path


def import_file(path: str, prefix: str):
    """The module of one ``.py`` file found by name (a block module, a reader
    file), imported under a name of its own and not through ``sys.path``."""
    stem = os.path.splitext(os.path.basename(path))[0]
    mod_spec = importlib.util.spec_from_file_location(prefix + re.sub(r"\W", "_", stem), path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module


def load_block(name: object, bench_dir: str = HERE):
    """Import a configuration's block module and check its parts. Only the
    process that runs the model calls this: a block module may import jax."""
    path = block_file(name, bench_dir)
    module = import_file(path, "chip_block_")
    missing = [part for part in BLOCK_PARTS if not hasattr(module, part)]
    if missing:
        raise SpecError(f"block module {path} lacks {missing} (a block module gives {list(BLOCK_PARTS)})")
    paths = module.kernel_paths
    if not isinstance(paths, dict) or not paths or not all(
        isinstance(k, str) and isinstance(v, int) and v >= 0 for k, v in paths.items()
    ):
        raise SpecError(f"block module {path}: kernel_paths maps each kernel path that 'correct' "
                        "requires (at least one) to its fewest dispatches")
    return module


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    layer: str = ""
    moves: str = ""
    reader: str = ""
    args: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config_file: str  # absolute path of configs/<config>.json
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: tuple[Metric, ...]
    per_layer: tuple[Metric, ...]


def _metric_entry(entry: dict, kind: str) -> Metric:
    name = check_name(entry.get("name"), f"{kind} metric")
    check_unit(entry.get("unit"), f"metric {name}")
    if entry.get("better") not in ("lower", "higher"):
        raise SpecError(f"metric {name}: 'better' must be lower or higher")
    if entry.get("source") not in SOURCES:
        raise SpecError(f"metric {name}: 'source' must be one of {SOURCES}")
    if kind == "end_to_end" and entry["source"] not in ("host_clock", "device_trace"):
        raise SpecError(f"end-to-end metric {name} must come from host_clock or device_trace")
    return Metric(
        name=name,
        unit=entry["unit"],
        better=entry["better"],
        source=entry["source"],
        layer=str(entry.get("layer", "")),
        moves=str(entry.get("moves", "")),
    )


def load_benchmark(root: str = ROOT) -> dict:
    bm = _read_json(os.path.join(root, "BENCHMARK.json"))
    for key in ("command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"):
        if key not in bm:
            raise SpecError(f"BENCHMARK.json lacks '{key}'")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [check_name(e.get("name"), group) for e in bm[group]]
        if len(set(names)) != len(names):
            raise SpecError(f"BENCHMARK.json: duplicate name in '{group}'")
    e2e = {m["name"] for m in bm["end_to_end"]}
    if "setup_s" not in e2e:
        raise SpecError("BENCHMARK.json: end_to_end must include setup_s")
    cells = {w["name"] for w in bm["workloads"]}
    for m in bm["end_to_end"]:
        _metric_entry(m, "end_to_end")
    for m in bm["per_layer"]:
        _metric_entry(m, "per_layer")
        if m.get("moves") not in e2e:
            raise SpecError(f"metric {m['name']}: 'moves' names no end-to-end metric")
        for w in m.get("workloads", []):
            if w not in cells:
                raise SpecError(f"metric {m['name']}: unknown workload {w!r}")
    for w in bm["workloads"]:
        check_name(w.get("config"), f"workload {w['name']} config")
        check_name(w.get("traffic"), f"workload {w['name']} traffic")
        if w.get("chips") not in (1, 4):
            raise SpecError(f"workload {w['name']}: chips must be 1 or 4")
    return bm


def load_cell(name: str, root: str = ROOT) -> Cell:
    """Everything one cell needs, found by the names in ``BENCHMARK.json``."""
    bm = load_benchmark(root)
    entry = next((w for w in bm["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SpecError(
            f"no workload {name!r} in BENCHMARK.json (has: "
            f"{[w['name'] for w in bm['workloads']]})"
        )
    cfg_entry = next((c for c in bm["configs"] if c["name"] == entry["config"]), None)
    if cfg_entry is None:
        raise SpecError(f"workload {name}: no config {entry['config']!r} in BENCHMARK.json")
    bench_dir = os.path.join(root, bm["paths"][0])
    config_file = os.path.join(root, cfg_entry["file"])
    config = _read_json(config_file)
    for key in cfg_entry.get("reduced", []):
        check_name(key, f"config {cfg_entry['name']} reduced key")
        if key not in config:
            raise SpecError(f"config {cfg_entry['name']}: reduced key {key!r} is not in its file")
    block_file(config.get("module"), bench_dir)
    traffic = _read_json(os.path.join(bench_dir, "traffic", entry["traffic"] + ".json"))

    def in_cell(m: dict) -> bool:
        return "workloads" not in m or name in m["workloads"]

    e2e = tuple(_metric_entry(m, "end_to_end") for m in bm["end_to_end"] if in_cell(m))
    per_layer = []
    for m in bm["per_layer"]:
        if not in_cell(m):
            continue
        base = _metric_entry(m, "per_layer")
        f = _read_json(os.path.join(bench_dir, "metrics", base.name + ".json"))
        for key in ("unit", "layer", "moves"):
            if key in f and f[key] != m.get(key):
                raise SpecError(
                    f"metric {base.name}: its file says {key}={f[key]!r}, "
                    f"BENCHMARK.json says {m.get(key)!r}"
                )
        if not isinstance(f.get("reader"), str):
            raise SpecError(f"metric {base.name}: its file names no 'reader'")
        per_layer.append(
            dataclasses.replace(base, reader=f["reader"], args=dict(f.get("args", {})))
        )
    return Cell(
        name=name,
        chips=int(entry["chips"]),
        config_name=entry["config"],
        config_file=config_file,
        config=config,
        traffic_name=entry["traffic"],
        traffic=traffic,
        end_to_end=e2e,
        per_layer=tuple(per_layer),
    )
