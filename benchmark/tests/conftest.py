"""The benchmark's own tests run on the CPU: ``python -m pytest
benchmark/tests -q``. They never look for a chip."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

_HERE = os.path.dirname(os.path.abspath(__file__))
for p in (_HERE, os.path.dirname(_HERE),
          os.path.dirname(os.path.dirname(_HERE))):
    if p not in sys.path:
        sys.path.insert(0, p)
