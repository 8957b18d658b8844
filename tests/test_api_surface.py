"""The public API surface (repro.api.__all__) is a contract: everything
in it must import, and no signature may drift without an intentional
update of the golden snapshot.

Regenerate the snapshot after an INTENTIONAL surface change with

    PYTHONPATH=src python tests/test_api_surface.py --regen
"""
from __future__ import annotations

import dataclasses
import inspect
import json
import os

GOLDEN = os.path.join(os.path.dirname(__file__), "data", "api_surface.json")


def _surface() -> dict:
    """``{qualname: signature-or-field-list}`` of everything public in
    ``repro.api.__all__`` — functions and public methods by
    ``inspect.signature``, dataclasses additionally by their ordered
    ``(field, type)`` list (a renamed or retyped result field is surface
    drift even though no signature changes)."""
    import repro.api as api

    out: dict = {"__all__": sorted(api.__all__)}
    for name in api.__all__:
        obj = getattr(api, name)  # ImportError/AttributeError = failure
        if isinstance(obj, type):
            if dataclasses.is_dataclass(obj):
                out[f"{name}.__fields__"] = [
                    f"{f.name}: {getattr(f.type, '__name__', f.type)}"
                    for f in dataclasses.fields(obj)
                ]
            for mname, meth in sorted(vars(obj).items()):
                if mname.startswith("_") and mname != "__init__":
                    continue
                if callable(meth):
                    out[f"{name}.{mname}"] = str(inspect.signature(meth))
                elif isinstance(meth, property):
                    out[f"{name}.{mname}"] = "<property>"
        elif callable(obj):
            out[name] = str(inspect.signature(obj))
        else:
            out[name] = repr(obj)
    return out


def test_api_all_imports_and_signatures_match_golden():
    with open(GOLDEN) as f:
        golden = json.load(f)
    current = _surface()
    assert current == golden, (
        "repro.api surface drifted from tests/data/api_surface.json.\n"
        "If the change is intentional, regenerate with\n"
        "  PYTHONPATH=src python tests/test_api_surface.py --regen\n"
        + "\n".join(
            f"  {k}: {golden.get(k)!r} -> {current.get(k)!r}"
            for k in sorted(set(golden) | set(current))
            if golden.get(k) != current.get(k)
        )
    )


def test_package_lazy_reexports():
    """``repro.TriangleEngine`` et al. resolve lazily (no jax import at
    bare-package import time, so a script can still set ``XLA_FLAGS``)."""
    import importlib
    import subprocess
    import sys

    import repro

    api = importlib.import_module("repro.api")
    for name in repro._API_EXPORTS:
        assert getattr(repro, name) is getattr(api, name)
    # a bare `import repro` must not pull in jax
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys, repro; sys.exit('jax' in sys.modules)"],
        env={**os.environ,
             "PYTHONPATH": src + os.pathsep + os.environ.get("PYTHONPATH", "")},
        capture_output=True,
    )
    assert out.returncode == 0, "import repro must stay jax-free"


if __name__ == "__main__":
    import sys

    if "--regen" in sys.argv:
        os.makedirs(os.path.dirname(GOLDEN), exist_ok=True)
        with open(GOLDEN, "w") as f:
            json.dump(_surface(), f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {GOLDEN}")
    else:
        sys.exit("usage: python tests/test_api_surface.py --regen")
