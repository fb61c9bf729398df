"""The harness's own modules are plain files of ``bench/``, found by path."""
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
