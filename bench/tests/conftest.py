"""Put the checkout's root and ``src`` on the path, so that the tests import
the harness as ``bench.harness`` and the program as ``repro``."""

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
