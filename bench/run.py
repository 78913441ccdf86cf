"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload iiot800k.ingest --seed 7 --seconds 10 \
        --trace 0

Runs on the machine it is started on and needs the cell's chips: where
JAX finds no TPU, or fewer chips than the cell asks for, it exits non-zero
and prints no result.  The last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``; ``check`` last, each compared number
beside its limit); the same numbers close standard error.
"""
import os
import sys
import tempfile
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))
# one fixed cache directory inside the checkout: the path is part of the
# cache key, and nothing is shared with another checkout
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
# the TPU runtime's logs go under this run's temporary directory
os.environ.setdefault("TPU_LOG_DIR",
                      os.path.join(tempfile.gettempdir(), "tpu_logs"))

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
