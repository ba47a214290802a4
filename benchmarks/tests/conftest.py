"""The benchmark's tests run on the CPU and never touch the repo's own
``tests/conftest.py``."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
