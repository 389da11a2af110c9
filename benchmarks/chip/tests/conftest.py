"""CPU tests of the chip benchmark's harness:

    PYTHONPATH=src python -m pytest -q benchmarks/chip/tests

The CPU stands in for the chip: four host devices for the four-chip cell,
graphs of a few hundred vertices. Nothing here measures time.
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))
