"""Path set-up for the benchmark's own tests: the checkout root (for the
``bench`` package) and ``src`` (the program) on ``sys.path``."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
