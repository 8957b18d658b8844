"""repro — cover-edge triangle counting (Bader et al., cs.DC 2022) in
JAX, on one device or a mesh.  See README.md / DESIGN.md.

The public front door is :mod:`repro.api` — re-exported lazily here
(``repro.TriangleEngine`` etc.) so that importing the bare package stays
free of jax side effects (a script may still set ``XLA_FLAGS`` after it,
before the first jax import).
"""

__version__ = "1.0.0"

_API_EXPORTS = (
    "TriangleEngine", "TCOptions", "TriangleReport", "Overflow",
    "default_engine", "ROUTES",
)

__all__ = list(_API_EXPORTS) + ["api"]


def __getattr__(name):
    if name == "api" or name in _API_EXPORTS:
        import repro.api as api

        return api if name == "api" else getattr(api, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
