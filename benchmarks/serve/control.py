"""A benchmark run that also reads the control.

    python3 benchmarks/serve/control.py --workload <cell> --seed <n> \\
        --seconds <s> --trace 0

Runs the cell exactly as ``bench.py`` does and, at the same positions of
the same sampled requests, reads the gap of the token that the plain
reference computed in float8 (e4m3) puts first: the control that a
limit on ``served_gap_max`` must fail (see ``servebench/check.py``).
The benchmark's own runs never run it.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import bench  # noqa: E402

if __name__ == "__main__":
    sys.exit(bench.main(sys.argv[1:], control=True))
