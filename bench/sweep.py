"""Find a cell's knee: the highest offered rate the engine sustains.

    python -m bench.sweep --workload <cell> --seed <n> --seconds <s> \\
        --rates 0.5,1,1.5,2

One process and one set-up; each rate then runs the cell's traffic mix
open-loop for ``--seconds``, and the streams still open are drained
before the next rate. The backlog at time t is the number of requests
due by t and not yet admitted (no dispatch that escaped a shed). A rate
is sustained when the backlog does not grow over the window: its mean
over the last third is at most one request above its mean over the
middle third. The knee is the highest sustained rate below the first
that is not. Each rate prints one JSON line; the last line is the knee.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

os.environ.setdefault("TPU_LOG_DIR", "disabled")

from . import model, serve, spec, traffic  # noqa: E402
from .client import OpenLoop  # noqa: E402
from .measure import Run, percentile  # noqa: E402


def backlog(run: Run, t: float) -> int:
    return sum(1 for r in run.records if r.due <= t and (
        r.admitted_at is None or r.admitted_at > t))


def sustained(run: Run) -> tuple:
    """(sustained?, mean backlog middle third, mean backlog last third)"""
    ts = np.linspace(run.t0, run.end, 61)[1:]
    b = np.array([backlog(run, t) for t in ts], np.float64)
    mid, last = b[20:40].mean(), b[40:].mean()
    return bool(last <= mid + 1.0), float(mid), float(last)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, req/s, ascending")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    _, _, err = serve.start(cell.chips, args.rehearse)
    if err:
        print(f"sweep: {err}", file=sys.stderr)
        return 2
    m = model.flat(cell.config, args.rehearse)
    mix = cell.traffic
    eng, stubs, _ = serve.prepare(m, mix, args.seed,
                               "interpret" if args.rehearse else "tpu")
    knee = None
    try:
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            reqs = traffic.schedule(mix, args.seed + i, args.seconds,
                                    m["vocab_size"], rate=rate,
                                    prompt_div=m["prompt_divisor"],
                                    output_div=m["output_divisor"])
            loop = OpenLoop(stubs, reqs)
            steps0 = eng.stream_steps
            loop.run(args.seconds)
            steps = eng.stream_steps - steps0
            run = Run(m=m, records=loop.records, t0=loop.t0, end=loop.end,
                      setup_s=0.0)
            ok, mid, last = sustained(run)
            loop.finish()
            print(json.dumps({
                "rate": rate, "sustained": ok, "backlog_mid": mid,
                "backlog_last": last, "due": len(run.due()),
                "admitted": sum(1 for r in run.due()
                                if r.admitted_at is not None
                                and r.admitted_at < run.end),
                "sheds": loop.sheds,
                "offered_tok_per_s": sum(r.req.max_new for r in run.due())
                / run.seconds,
                "out_tok_per_s": run.tokens_received() / run.seconds,
                "streams_per_step": len(run.decode_lives()) / max(steps, 1),
                "peak_pages": run.peak_pages(),
                "ttft_p90_ms": percentile(run.ttfts_ms(), 90),
                "itl_p95_ms": percentile(run.gaps_ms(), 95)}), flush=True)
            if not ok:
                break
            knee = rate
    finally:
        serve.release(eng)
    print(json.dumps({"workload": args.workload, "knee_req_per_s": knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
