import os
import sys
from pathlib import Path

# tests run on the single real CPU device; only dryrun.py forces 512.
sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))


def pytest_addoption(parser):
    parser.addoption(
        "--paper-peaks", action="store_true", default=False,
        help="run the long-horizon peak drift gates of "
             "tests/test_paper_peaks.py (minutes to hours on a CPU)")
