"""perf/tests run on the CPU: ``python -m pytest perf/tests -q``.

They check the benchmark's own arithmetic and walk ``perf/run.py`` end to
end through throw-away cells (``perf/tests/cells``) at toy sizes.  A CPU run
says whether results are right and what is counted; it never gives a time.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
PERF_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(PERF_DIR)
for p in (PERF_DIR, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)
