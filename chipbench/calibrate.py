"""Readings for a cell's ``correct`` limit: for each seed, one run of the
cell at its own load (a short window, long enough to finish the mix's
longest requests) in which the float8 control takes the program's place in
the comparison, all in one process so set-up is paid once per seed and
compiled once.

    python chipbench/calibrate.py --workload <cell> --seconds 30 \
        --seeds 1 2 3

Prints one JSON line per seed: the program's widest logit gap and its
verdict, the control's gap and the harness's verdict on the control (which
has to be false), and how many tokens and moved requests were compared.
Exits 1 if the control came out correct on any seed.  The limit in
``chipbench/cells/<cell>.json`` is set from these readings by hand
(PERF.md gives them)."""

from __future__ import annotations

import argparse
import json
import sys
import time

import run


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    passed = []
    for seed in args.seeds:
        t = time.perf_counter()
        try:
            res = run.run_cell(args.workload, seed, args.seconds, False,
                               control=True, t_start=t)
        except run.NoChip as e:
            print(e, file=sys.stderr)
            return 3
        c = res["checks"]
        passed.append(res["correct"])
        print(json.dumps({
            "seed": seed, "gap": res["program"]["max_logit_gap"],
            "program_correct": res["program"]["correct"],
            "control_gap": c["max_logit_gap"]["value"],
            "control_correct": res["correct"],
            "limit": c["max_logit_gap"]["limit"],
            "tokens": c["tokens_compared"]["value"],
            "moved": c.get("moved_requests_compared", {}).get("value"),
            "metrics": res["metrics"], "counts": res["counts"],
            "s": time.perf_counter() - t}),
            flush=True)
    if any(passed):
        print("calibrate: the control came out correct", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
