"""The benchmark's files, found by name under ``bench/``."""

from __future__ import annotations

import importlib.util
import json
import os
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class BenchError(RuntimeError):
    """The benchmark's own files or arguments are wrong."""


def load_json(path: Path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_module(path: Path):
    if not path.is_file():
        raise BenchError(f"missing benchmark file {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
