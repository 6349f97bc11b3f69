"""The architecture a configuration file names.

``"arch": "<name>"`` in the file selects ``bench/arch/<name>.py``, the
shapes of that family of models as the harness needs them (its contract
is in ``bench/arch/dense_gqa.py``'s docstring), just as ``"reference"``
selects ``bench/references/<name>.py``.  There is no default.
"""
from __future__ import annotations

import importlib
import re
from pathlib import Path

ARCH_DIR = Path(__file__).resolve().parent.parent / "arch"
NAME = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")      # an importable module


class CellError(RuntimeError):
    """A cell that cannot run as its files describe it."""


def module(cfg: dict):
    """The arch module the configuration names; a missing or unknown
    name is a ``CellError`` that names the file looked for."""
    name = cfg.get("arch")
    if name is None:
        raise CellError(f"the configuration names no \"arch\", the module "
                        f"{ARCH_DIR}/<arch>.py that gives its shapes")
    path = ARCH_DIR / f"{name}.py"
    if not (isinstance(name, str) and NAME.fullmatch(name)
            and path.is_file()):
        raise CellError(f"the configuration's arch {name!r} has no module "
                        f"{path}")
    return importlib.import_module(f"bench.arch.{name}")
