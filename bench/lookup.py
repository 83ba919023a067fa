"""A part of the benchmark found on disk by its name.

Metric readers (``bench/metrics/<metric>.py``), job entries
(``bench/entries/<entry>.py``) and the reference of each policy family
(``bench/references/<family>.py``) are modules loaded from their file, so
that a later cell brings its own as a new file and edits none.
"""

from __future__ import annotations

import importlib.util
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def load(kind: str, name: str, root: Path = ROOT):
    """The module ``<root>/bench/<kind>/<name>.py``; a missing file fails
    with its expected path."""
    path = root / "bench" / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module named {name!r}: "
                                f"expected {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
