"""The benchmark's tests import its library (``bench/benchlib``) and its
command (``bench/run.py``)."""

import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[2] / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
