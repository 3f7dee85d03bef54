"""Rate sweep of an open-loop cell: the highest Poisson rate the engine
sustains without a growing backlog, found once on the chip and written into
the cell's file as a number (the benchmark itself never searches).

    python chipbench/sweep.py --workload <cell> --rates 1.0 1.5 2.0 \
        --seconds 30

One process, one engine and one set of weights (seed ``--seed``); the rates
run in the order given, each as a run of the cell drives it (warm-up
traffic, a window of ``--seconds``, then the wait for the first tokens of
the window's arrivals), on top of what the previous rate left in flight.  Prints one
JSON line per rate: arrivals and completions per second in the window, the
waiting queue at the window's start and end, and the TTFT / TPOT p90."""

from __future__ import annotations

import argparse
import json
import sys

import run


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    spec = run.load_cell(args.workload)
    run.setup_jax()
    try:
        run.require_chip(int(spec["cell"]["chips"]))
    except run.NoChip as e:
        print(e, file=sys.stderr)
        return 3
    from chipbench import drive, measure
    cfg, mix = spec["cfg"], spec["mix"]
    eng, _ = run.build_engine(cfg, args.seed)
    for rate in args.rates:
        drv = drive.Driver(eng, mix, args.seed, rate)
        win = drv.run(float(mix["warmup_s"]), args.seconds, None)
        r = measure.Run(cfg=cfg, mix=mix, peak={}, setup_s=0.0, driver=drv)
        done = [q for q in drv.by_rid.values()
                if q.done is not None and win.t0 <= q.done <= win.t1]
        ttft, tpot = measure.p90(r.ttft_s()), measure.p90(r.tpot_s())
        print(json.dumps({
            "rate": rate, "seconds": r.window_s,
            "arrivals_per_s": len(r.window_arrivals()) / r.window_s,
            "completions_per_s": len(done) / r.window_s,
            "tok_per_s": r.tokens / r.window_s,
            "queue_start": win.counters0["queued"],
            "queue_end": win.counters1["queued"],
            "ttft_p90_ms": None if ttft is None else ttft * 1e3,
            "tpot_p90_ms": None if tpot is None else tpot * 1e3}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
