"""Pieces of the benchmark found by name.

Each piece a configuration, a traffic mix or ``BENCHMARK.json`` names is
one Python file in a directory of its kind, and a later change adds a
file rather than editing one:

* ``generators/<name>.py``  a database generator (``data.generator``)
* ``engines/<name>.py``     how the program is built and served
* ``loops/<name>.py``       how queries are sent (open, closed)
* ``arrivals/<name>.py``    the gaps of an open loop
* ``bases/<name>.py``       which database graphs queries start from
* ``queries/<name>.py``     what a query asks, and how its answer is judged
* ``metrics/<name>.py``     a metric reader, ``read(run) -> float | None``
"""
from __future__ import annotations

import importlib.util
import os
import re
import sys
from types import ModuleType
from typing import Dict, Optional, Tuple

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
KINDS = ("generators", "engines", "loops", "arrivals", "bases", "queries",
         "metrics")
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
_loaded: Dict[Tuple[str, str, str], ModuleType] = {}


class Missing(LookupError):
    """No file of that kind under that name."""


def load(kind: str, name: str, bench_dir: Optional[str] = None
         ) -> ModuleType:
    """The module ``<bench_dir>/<kind>/<name>.py``, loaded once."""
    if kind not in KINDS:
        raise ValueError(f"unknown kind of piece {kind!r}")
    if not isinstance(name, str) or not _NAME.match(name):
        raise Missing(f"{kind}: {name!r} is not a name")
    bench_dir = bench_dir or BENCH_DIR
    path = os.path.join(bench_dir, kind, f"{name}.py")
    key = (bench_dir, kind, name)
    if key in _loaded:
        return _loaded[key]
    if not os.path.isfile(path):
        raise Missing(f"no {kind}/{name}.py")
    mod_name = "chipbench_{}_{}".format(kind, re.sub(r"\W", "_", name))
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    _loaded[key] = mod
    return mod
