import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, BENCH_DIR)
if ROOT not in sys.path:
    sys.path.append(ROOT)
