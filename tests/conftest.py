"""Test-session wiring: make ``repro`` importable without ``PYTHONPATH=src``
(e.g. a bare ``pytest``)."""
import importlib.util
import os
import sys

_SRC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")
if _SRC not in sys.path and importlib.util.find_spec("repro") is None:
    sys.path.insert(0, _SRC)
