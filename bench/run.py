"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``.  It needs a
TPU with as many chips as the cell asks for, and exits non-zero without
one.  With ``--trace 0`` the result holds the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics read from a profiler trace.
"""
import sys
import time

T0 = time.time()

if __name__ == "__main__":
    import os
    import tempfile
    from pathlib import Path
    # libtpu logs under this run's own TMPDIR, not a path shared by runs
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from bench.harness import main
    sys.exit(main(t0=T0))
