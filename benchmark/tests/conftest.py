"""The benchmark's own tests run on the CPU: ``python -m pytest benchmark/tests``
from the root of the checkout (not part of tier-1 yet; see PERF.md)."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
