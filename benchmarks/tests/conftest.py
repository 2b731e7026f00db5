"""The harness's tests run on the CPU at tiny sizes:

    python -m pytest benchmarks/tests -q

The platform is environment and nothing else, set before jax is imported
(the rule of ``tests/conftest.py``); four virtual devices for the cells
that span chips."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from theanompi_tpu import cachedir  # noqa: E402  (import-light, no jax)

os.environ["XLA_FLAGS"] = cachedir.cpu_xla_flags(
    os.environ.get("XLA_FLAGS", ""), fake_devices=4
)
