"""Read the numbers a cell's correctness limits are set from, on the chip.

    python3 chipbench/calibrate.py --workload <cell> --seconds 3 \
        --seeds 101,102,... --control-seeds 101,102,103

For each seed, one run of the cell in this process (the timed path at the
cell's own size, for a short window), printing the numbers compared; on
the control seeds it also prints the control's reading (the program's own
bfloat16 path, or the reference in bfloat16 where the program has none).
The largest sound reading is a limit's lower end, the smallest control
reading its upper end.  One JSON line per seed, then a summary line.
The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    sys.path[:] = [p for p in sys.path
                   if Path(p or ".").resolve() != ROOT / "chipbench"]
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench.manifest import Manifest
    from chipbench.run import enable_compile_cache, require_tpu, run_cell
    devices = require_tpu(Manifest.load(ROOT).cell(args.workload)["chips"])
    enable_compile_cache(ROOT)
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    sound, low = {}, {}
    for seed in (int(s) for s in args.seeds.split(",")):
        result, outcome, _ = run_cell(ROOT, args.workload, seed, args.seconds,
                                   False, devices=devices,
                                   control=seed in controls)
        for name, c in result["checks"].items():
            sound.setdefault(name, []).append(c["value"])
        for name, v in outcome.control.items():
            low.setdefault(name, []).append(v)
        print(json.dumps({"seed": seed, "correct": result["correct"],
                          "checks": result["checks"],
                          "control": outcome.control,
                          "metrics": result["metrics"]}), flush=True)
    print(json.dumps({"workload": args.workload,
                      "lower": {k: max(v) for k, v in sound.items()},
                      "upper": {k: min(v) for k, v in low.items()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
