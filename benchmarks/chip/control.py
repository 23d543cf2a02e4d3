#!/usr/bin/env python3
"""Read a cell's correctness numbers beside those of its control: the
plain reference computed with every matmul weight rounded to float8, put
in the program's place (its own first pick at each served position).

    python3 benchmarks/chip/control.py --workload <name> --seed <n> \\
        --seconds <s>

One run of the cell as ``run.py`` makes it, then both readings, each held
to the cell's limits by the same comparison.  The last line is the
result with the control's numbers under ``checks`` and its verdict under
``correct`` (false where the limits separate the two), and the program's
under ``program``.  The limits in ``cells/<name>.json`` lie between the
program's readings and the control's (PERF.md gives both).  Needs a TPU;
``run.py`` never runs this.
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
# libtpu writes its logs to a fixed /tmp directory unless told otherwise.
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(HERE.parents[1] / "src"))
sys.path.insert(0, str(HERE))


def main(argv=None) -> int:
    import harness
    import run

    args = run.parse(argv)
    args.control = True
    try:
        result = harness.run(run.ROOT, args, T_START)
    except harness.NoChip as e:
        print(f"control.py: {e}", file=sys.stderr)
        return 2
    harness.print_result(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
