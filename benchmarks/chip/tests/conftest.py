import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))
for p in (os.path.join(ROOT, "src"), BENCH, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)
