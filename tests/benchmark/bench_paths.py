"""Where the benchmark's tests find things; importing this puts the
checkout first on `sys.path`, so that `benchmark` is the checkout's
package and not this directory."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TINY = os.path.join(ROOT, "tests", "benchmark", "tiny")
DATA = os.path.join(ROOT, "tests", "benchmark", "data")

if ROOT not in sys.path[:1]:
    sys.path.insert(0, ROOT)
