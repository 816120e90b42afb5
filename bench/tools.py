"""The control readings that set the comparison's limits; no run calls it.

    python3 bench/tools.py control --workload hg_mlp.offline_b2048 \
        --seeds 1,2,3 --seconds 3 --dtypes bfloat16,float8_e4m3fn

`control` runs the cell's timed path at its own size and load on each
seed, in one process, and prints the number compared (`wrong_rows`) of
the program and of the control: the reference computed in each lower
precision and put in the program's place.  Each line is one JSON
object, also appended to `bench_out/tools.jsonl` (not committed).
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run as R  # noqa: E402

OUT = R.ROOT / "bench_out" / "tools.jsonl"


def emit(row: dict) -> None:
    line = json.dumps(row)
    print(line, flush=True)
    OUT.parent.mkdir(exist_ok=True)
    with OUT.open("a") as f:
        f.write(line + "\n")


def control(args) -> None:
    for seed in (int(s) for s in args.seeds.split(",")):
        res, ctx = R.run(args.workload, seed, args.seconds, False)
        req = getattr(ctx.driver, "req", None)  # served drivers only
        row = {"tool": "control", "workload": args.workload, "seed": seed,
               "program": res["checks"],
               "checked": req.n_checked if req else None,
               "metrics": res["metrics"]}
        for dt in args.dtypes.split(","):
            row[f"control_{dt}"] = ctx.driver.check(dt)
        emit(row)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    sub = ap.add_subparsers(dest="tool", required=True)
    c = sub.add_parser("control")
    c.add_argument("--seeds", required=True)
    c.add_argument("--dtypes", default="bfloat16")
    c.add_argument("--workload", required=True)
    c.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    control(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
