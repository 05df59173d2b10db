"""Run one cell traced and keep what the trace shows.

    python3 benchmarks/chip/trace_cell.py --workload NAME --seed N --seconds S [--out DIR]

One run as ``run_cell.py --trace 1`` makes it (``harness.execute``), with
no comparison with the reference afterwards: ``correct`` is null. The last
line on standard output is the result. With ``--out`` the trace, the
compiled HLO text of each program the window ran and the values the
per-layer readers got (both reductions of the trace, the program's
counters) are written into that directory. Without a TPU it exits 3.
"""

import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

from chipbench import harness  # noqa: E402
from chipbench.spec import resolve  # noqa: E402


def main(argv=None) -> int:
    ap = harness.arguments("trace_cell.py")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    cell = resolve(args.workload)
    chip = harness.on_chip(cell)
    if chip is None:
        return harness.EXIT_NO_CHIP
    devices, info, peaks = chip
    result = harness.execute(cell, args.seed, args.seconds, True, devices, info, peaks=peaks,
                             check_outputs=False, keep=args.out)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
