"""Measurements that define a cell, each in one process so that set-up and
compilation are paid once.  Benchmark runs never call this.

    python3 bench/tune.py sweep  --workload <cell> --seed <n> --seconds <s> --rates 0.3,0.4,...
    python3 bench/tune.py limits --workload <cell> --seeds <a,b,...> --seconds <s>

``sweep`` runs the cell's mix at each offered rate (requests/s) and
prints the end-to-end metrics and whether the queue grew: the knee is the
highest rate with no growing backlog.  ``limits`` runs the cell on each
seed and prints the program's widest logit gap and the float8 control's
on the same served tokens: the two readings a limit is set between.
Each line printed is one JSON object.
"""
import argparse
import dataclasses
import json
import os
import sys
import time

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import registry  # noqa: E402
from bench.run import run_cell  # noqa: E402


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("mode", choices=("sweep", "limits"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--rates", default="")
    args = ap.parse_args(argv)
    cell = registry.cell(args.workload)
    if args.mode == "sweep":
        runs = [(args.seed, float(r)) for r in args.rates.split(",")]
    else:
        runs = [(int(s), cell.traffic["rate_per_s"])
                for s in args.seeds.split(",")]
    for seed, rate in runs:
        c = dataclasses.replace(
            cell, traffic=dict(cell.traffic, rate_per_s=rate))
        t = time.perf_counter()
        res = run_cell(c, seed, args.seconds, False, t_start=t,
                       control=args.mode == "limits", log=_log)
        print(json.dumps({"seed": seed, "rate_per_s": rate,
                          "correct": res["correct"],
                          "attempted": res["attempted"],
                          "metrics": {k: v["value"]
                                      for k, v in res["metrics"].items()},
                          "window": res["window"],
                          "checks": res["checks"],
                          "peak_bytes": res["device"]["memory_peak_bytes"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
