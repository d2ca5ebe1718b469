"""The benchmark's files found by name: ``bench/<kind>/<name>.py`` of a
checkout, loaded by path, so that a file a later change adds is found with
no edit elsewhere.

* ``generators/<generator>``: ``arcs(config, key)``, the arcs of a
  configuration's graph (``bench/gen.py`` builds the CSR around them);
* ``entries/<entry>``: the closed-loop client a traffic mix drives, its
  plain reference, control, comparison and limits;
* ``metrics/<metric>``: ``read(ctx)``, one metric of ``BENCHMARK.json``,
  end-to-end or per-layer.
"""
from __future__ import annotations

import importlib.util
import re
from pathlib import Path


def module(root: Path, kind: str, name: str):
    """``<root>/bench/<kind>/<name>.py`` as a module; exits when missing."""
    path = root / "bench" / kind / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"bench: no file bench/{kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{re.sub(r'[^0-9A-Za-z_]', '_', name)}", path)
    found = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(found)
    return found
