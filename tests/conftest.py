import os
import pathlib
import sys

# Make the src/ layout importable even when the package is not pip-installed
# and PYTHONPATH is unset (pytest>=7 also honors `pythonpath` in
# pyproject.toml; this covers direct `python -m pytest` from any cwd).
_SRC = str(pathlib.Path(__file__).resolve().parent.parent / "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

# Prefer the real hypothesis (declared in pyproject's [test] extra); fall back
# to the deterministic in-repo shim in hermetic environments without it.
try:
    import hypothesis  # noqa: F401
except ImportError:
    from repro._compat import hypothesis_fallback

    hypothesis_fallback.install()

# Tests must see exactly ONE device (tests that need a mesh force host
# devices in a child process); keep any ambient XLA_FLAGS from leaking in.
os.environ.pop("XLA_FLAGS", None)

import jax  # noqa: E402

jax.config.update("jax_platform_name", "cpu")
