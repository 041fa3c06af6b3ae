"""Test bootstrap: make ``repro`` importable without the PYTHONPATH=src
hack, and keep the planner's measured artifacts out of the tests."""

import os
import sys

_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "src")
if _SRC not in sys.path:
    try:
        import repro  # noqa: F401
    except ImportError:
        sys.path.insert(0, _SRC)

# Hermetic tests: the repo commits measurement artifacts
# (experiments/calibration.json, experiments/tuning.json) that deliberately
# shift planner output when present.  The suite must assert the *analytic*
# behavior regardless of which artifacts happen to be checked in, so point
# both env overrides at a path that never exists; artifact-dependent tests
# (test_calibration.py, test_tune.py) monkeypatch these per-test to real
# tmp files, which takes precedence over this default.
os.environ.setdefault("REPRO_CALIBRATION", os.devnull + ".absent")
os.environ.setdefault("REPRO_TUNING", os.devnull + ".absent")
