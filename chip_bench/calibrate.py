"""Read the numbers ``correct`` is decided by, for setting a cell's limits.

Not part of a benchmark run.  In one process (a chip belongs to one process)
it makes one whole run of the cell per seed (:func:`chip_bench.run.run_cell`,
the window one study long) and prints every number the study's check read;
the control seeds make the same run with the control (the float32 FIFO
reference in the replay's place), which the limits must refuse::

    python chip_bench/calibrate.py --workload <cell> --seeds 1,2,3 --control-seeds 4,5,6

The largest number over the sound seeds is a limit's lower reading, the
smallest over the control seeds its upper reading.  Each line is JSON; the
last line gathers them, and ``--out`` writes them to a file as well.
``--cpu`` skips the look for a chip: readings of the numpy replay that
``backend="auto"`` picks on a CPU.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from chip_bench.reference import control_replay  # noqa: E402
from chip_bench.run import run_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    out = []
    for seeds, substitute in ((args.seeds, None),
                              (args.control_seeds, control_replay)):
        for s in filter(None, seeds.split(",")):
            result, numbers = run_cell(args.workload, int(s), 0.0, False,
                                       require_tpu=not args.cpu,
                                       substitute=substitute)
            line = {"workload": args.workload, "seed": int(s),
                    "control": substitute is not None,
                    "correct": result["correct"], "numbers": numbers}
            print(json.dumps(line), flush=True)
            out.append(line)
    summary = {}
    for line in out:
        side, agg = (("control_min", min) if line["control"]
                     else ("sound_max", max))
        for k, v in line["numbers"].items():
            summary.setdefault(k, {})
            summary[k][side] = agg(summary[k].get(side, v), v)
    print(json.dumps({"summary": summary}), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps({"runs": out, "summary": summary},
                                             indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
