"""Find a part of the benchmark by its name: one file under ``bench/<kind>/``.

Configurations and mixes are data (``bench/configs``, ``bench/mixes``).
The code that a mix or ``BENCHMARK.json`` names is one file per name:

* ``bench/ops/<op>.py``: what one request does, the set-up it needs and the
  check of its answers (class ``Op``);
* ``bench/plans/<plan>.py``: the requests, in the order they are sent
  (function ``requests``);
* ``bench/loops/<loop>.py``: how the clients send them (function ``run``);
* ``bench/metrics/<metric>.py``: one per-layer metric (function ``read``).

A new part is a new file: no file that exists is edited.
"""
from __future__ import annotations

import importlib.util
import re
from pathlib import Path


def load(root: Path, kind: str, name: str):
    """The module ``bench/<kind>/<name>.py`` of the checkout at ``root``."""
    path = root / "bench" / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no part {name!r} of kind {kind!r}: {path} is missing")
    module_name = "bench_" + re.sub(r"\W", "_", f"{kind}_{name}")
    spec = importlib.util.spec_from_file_location(module_name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
