"""The benchmark's own tests: CPU only, tiny sizes. Run from the root
of the repo: ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``."""
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))
