"""The family module a configuration names by its `reference` key:
`bench/configs/<reference>.py`. It is the one place that knows the
architecture; the harness reaches the architecture only through it.

A family module exports `arch_config(cfg)` (the program's ArchConfig),
`builder(cfg)` (a function of a root key that returns the program's
serving tree), `linear_shapes(cfg)` ((K, N) of each linear of a layer)
and `served_gaps(seed, cfg, seqs)` (the plain reference's comparison).
`dense_gqa.py` lists the rest of its exports, which its tests use.
"""

from __future__ import annotations

import functools
import importlib.util
from pathlib import Path

CONFIGS = Path(__file__).resolve().parent / "configs"


@functools.cache
def load(path: Path):
    """The module in a file, loaded once: a family module or a metric
    reader, whose names need not be Python identifiers."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def of(cfg: dict):
    """The family module of a configuration."""
    return load((CONFIGS / f"{cfg['reference']}.py").resolve())
