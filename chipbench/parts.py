"""Find a part of the benchmark by its name in BENCHMARK.json or in a
configuration or traffic file: ``chipbench/<kind>/<name>.py``.

Kinds: ``paths`` (a served path per placement), ``metrics`` (a reader
per per-layer metric), ``datasets`` (a collection generator per
``dataset``), ``queries`` (a query generator per traffic ``queries``)
and ``references`` (the plain reference per configuration
``reference``).  A new part is a new file; nothing existing is edited.
Each file is loaded once per process, so its jitted programs are
traced once.
"""
from __future__ import annotations

import functools
import importlib.util
from pathlib import Path

HERE = Path(__file__).resolve().parent


@functools.cache
def load(kind: str, name: str):
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} part {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(f"{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
