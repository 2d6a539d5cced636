"""The benchmark's own tests run on the host CPU, without a chip:

    JAX_PLATFORMS=cpu PYTHONPATH=src:. python -m pytest -q bench/tests
"""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (REPO, os.path.join(REPO, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
