"""The benchmark's own tests: host-only, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests

They are not among the repository's tests (``pytest.ini`` collects
``tests/``); the chip runs are made with ``run.py`` and ``control.py``.
"""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[3]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
