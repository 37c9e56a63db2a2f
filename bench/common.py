"""File lookup by name: configurations, model families, traffic mixes, cells,
metric readers, kernel counts and the table of peaks all live in files of
their own under ``bench/``, so a new cell, mix, configuration, architecture or
metric is added as files."""
from __future__ import annotations

import functools
import importlib.util
import json
import os
import re
from typing import Any, Dict

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def check_name(name: str) -> str:
    if not NAME_RE.match(name or ""):
        raise ValueError(f"not a valid name: {name!r}")
    return name


def load_json(kind: str, name: str, bench_dir: str = BENCH_DIR) -> Dict[str, Any]:
    """``bench/<kind>/<name>.json``, e.g. ``load_json("configs", "qwen2-1.5b")``."""
    path = os.path.join(bench_dir, kind, check_name(name) + ".json")
    with open(path) as f:
        return json.load(f)


def reader_path(kind: str, name: str, bench_dir: str = BENCH_DIR) -> str:
    """``bench/<kind>/<name>.py``, or, where there is none, the file of the
    name's stem before its first dot: a quantity split by the end-to-end
    metric it moves (``idle_share.classroom``, ``idle_share.whatsapp``)
    shares one reader, ``idle_share.py``."""
    path = os.path.join(bench_dir, kind, check_name(name) + ".py")
    if not os.path.exists(path) and "." in name:
        path = os.path.join(bench_dir, kind, name.split(".", 1)[0] + ".py")
    return path


def load_reader(kind: str, name: str, bench_dir: str = BENCH_DIR):
    """The module of ``reader_path`` (metric readers, kernel counts).
    Names may hold dots, so the file is loaded by path, not by import."""
    path = reader_path(kind, name, bench_dir)
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def _family(name: str, bench_dir: str):
    return load_reader("families", name, bench_dir)


def load_family(c: Dict[str, Any], bench_dir: str = BENCH_DIR):
    """The model family that configuration ``c`` names under ``"family"``:
    the module ``bench/families/<family>.py``, loaded once per file.  It
    gives the harness ``param_shapes(c)`` (the served weights' layout),
    ``logits(params, tokens, c, fp8=False)`` (the float32 reference and its
    float8 control), ``program_config(c)`` (the program's ``ModelConfig``),
    ``decode_weights(c)`` and ``decode_cost(c, pos)`` (one decode step's
    counts) and ``attention_layers(c)`` (the layers that call the paged
    decode kernel once a step).  There is no default family: a
    configuration without the key, or naming no file, is an error that
    names the path."""
    where = os.path.join(bench_dir, "families")
    if "family" not in c:
        raise KeyError(f"configuration {c.get('arch')!r} names no family: "
                       f"it needs a \"family\" key naming a file "
                       f"{where}/<family>.py")
    path = reader_path("families", c["family"], bench_dir)
    if not os.path.exists(path):
        raise FileNotFoundError(f"configuration {c.get('arch')!r} names "
                                f"family {c['family']!r}: no file {path}")
    return _family(c["family"], os.path.abspath(bench_dir))


def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def peaks(device_kind: str, bench_dir: str = BENCH_DIR) -> Dict[str, Any]:
    """Published peaks of one chip of ``device_kind``; a kind that is not in
    the table is an error, never a default."""
    with open(os.path.join(bench_dir, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json (known: {sorted(table['devices'])})")
    return table["devices"][device_kind]
