"""Tests of the benchmark (`benchmark/`): single-process, on the CPU mesh
`tests/conftest.py` sets up. The repo's root goes on the path so that
`benchmark` imports however pytest was started."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
