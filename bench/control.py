"""Readings of a cell's numbers compared, for the program and for the control.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 5

For each seed, one run of the cell as ``bench/run.py`` makes it (a
short window at the cell's own load and size), then the control: the
plain reference computed in bfloat16, the precision below the float32
the configuration states, put in the program's place and judged by the
same comparison.  Prints one JSON line per seed with both readings.
The limits in ``bench/configs`` sit between the largest program reading
and the smallest control reading.  The benchmark's own runs never run
this.
"""
import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, default=5.0)
    args = parser.parse_args()
    cell = harness.load_cell(args.workload)
    sys.path.insert(0, str(harness.ROOT / "src"))
    try:
        harness.check_device(cell.chips)
    except harness.NoAccelerator as exc:
        print(exc, file=sys.stderr)
        return 3
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run_cell(cell, seed, args.seconds, False,
                               started=time.perf_counter(), control=True)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "program": out["checks"],
                          "control": out["control"]}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
