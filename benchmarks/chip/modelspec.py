"""A configuration file and the modules a cell finds by name.

``configs/<name>.json`` keeps the published ``config.json`` keys (cut where
``reduced`` says) and may name its architecture (``"arch"``; ``dense``
where it does not). The architecture is the module ``archs/<arch>.py``: it
reads the configuration into its frozen, hashable ``Spec`` (``spec_of``),
maps it onto the program's ``ModelConfig`` (``model_config``), lays out the
weights (``layer_shapes``, ``top_shapes``, ``param_tree``), gives the plain
reference (``reference_embed``, ``reference_layer``, ``reference_head``)
and counts a step's work (``chunk_work``, ``decode_work``). A module is
looked for beside the cell files first, then with the benchmark.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType
from typing import Any, Dict

HERE = Path(__file__).resolve().parent
DEFAULT_ARCH = "dense"


def load(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def find(kind: str, name: str, bench_dir: Path = HERE) -> Path:
    """``<bench_dir>/<kind>/<name>.py`` where it exists, else the benchmark's own."""
    path = bench_dir / kind / f"{name}.py"
    return path if path.exists() else HERE / kind / f"{name}.py"


def module(path: Path, key: str) -> ModuleType:
    """The module at `path`, executed under `key` (its directory on the path,
    for the helpers beside it)."""
    if str(path.parent) not in sys.path:
        sys.path.append(str(path.parent))
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod  # a dataclass resolves its module while it is made
    spec.loader.exec_module(mod)
    return mod


def arch(name: str, bench_dir: Path = HERE) -> ModuleType:
    """The architecture module `name`, loaded once per file, so that every
    cell of one architecture shares its ``Spec`` class and compiled programs."""
    path = find("archs", name, bench_dir).resolve()
    key = f"bench_arch:{path}"
    return sys.modules.get(key) or module(path, key)


def arch_of(conf: Dict[str, Any], bench_dir: Path = HERE) -> ModuleType:
    return arch(conf.get("arch", DEFAULT_ARCH), bench_dir)
