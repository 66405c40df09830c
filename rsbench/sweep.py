"""Find a served cell's knee: run its window at several offered rates,
one after another in this process, and print one JSON line a rate.

    python3 rsbench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates <r> [<r> ...]

Each rate is a whole run of the cell (set-up, window, check) with the
mix's ``rate_per_s`` replaced.  The knee is the highest rate at which no
operation fails, the completed rate stays within 2% of the offered one
and the operations in flight do not grow from the window's first
quarter to its last; the cell then offers about 0.8 of it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

if __name__ == "__main__":
    sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from rsbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    harness.use_checkout_cache()
    from gpu_rscode_tpu.utils.backend import configure_compile_cache

    configure_compile_cache()
    for i, rate in enumerate(args.rates):
        lines: list[str] = []
        doc = harness.run_cell(
            args.workload, args.seed + i, args.seconds, False,
            overrides={"mix": {"rate_per_s": rate}},
            log=lambda s, **kw: lines.append(s))
        early = json.loads(lines[0])
        print(json.dumps({"rate_per_s": rate, "seed": args.seed + i,
                          "correct": doc["correct"],
                          "failed": doc["failed"],
                          "metrics": {k: v["value"]
                                      for k, v in doc["metrics"].items()},
                          "setup_parts": early["setup_parts"],
                          "window_compiles":
                              early["window_compiles"]["backend_compiles"],
                          "notes": early["notes"],
                          "checks": doc["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
