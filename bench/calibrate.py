"""Readings that a cell's limit on ``max_logit_gap`` is set from.

    python -m bench.calibrate --workload <cell> --seeds 1,2,3 --seconds <s>

One process. For each seed it builds the engine on that seed's weights,
serves the cell's traffic for ``--seconds`` as a run does, drains, frees
the engine, and reads on the same sample of served requests:

    program   the largest gap of a served token below the float32
              reference's best (what every run compares);
    control   the largest gap of the token that the reference computed
              through float8 puts first, the precision step below the
              bfloat16 the configuration serves in.

The lower reading of the limit is the program's largest over the seeds,
the upper one the control's smallest; each seed prints one JSON line and
the last line gives both. The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

from . import check, model, serve, spec, traffic  # noqa: E402
from .client import OpenLoop  # noqa: E402


def reading(cell, m, seed: int, seconds: float, backend: str) -> dict:
    mix = cell.traffic
    eng, stubs, _ = serve.prepare(m, mix, seed, backend)
    reqs = traffic.schedule(mix, seed, seconds, m["vocab_size"],
                            prompt_div=m["prompt_divisor"],
                            output_div=m["output_divisor"])
    loop = OpenLoop(stubs, reqs)
    loop.run(seconds)
    loop.finish()
    serve.release(eng, loop)
    del eng, stubs
    picked = check.sample(loop.records, seed)
    if not picked:
        return {"seed": seed, "program": None, "control": None}
    length = mix["max_total_tokens"] if m["prompt_divisor"] == 1 \
        else m["max_pages_per_seq"] * m["page_tokens"]
    prog = check.gaps(m, seed, picked, length)
    ctrl = check.control_gaps(m, seed, picked, length)
    return {"seed": seed, "program": float(prog.max()),
            "program_median": float(sorted(prog)[len(prog) // 2]),
            "control": float(ctrl.max()),
            "control_median": float(sorted(ctrl)[len(ctrl) // 2]),
            "tokens": int(prog.size)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    _, _, err = serve.start(cell.chips, args.rehearse)
    if err:
        print(f"calibrate: {err}", file=sys.stderr)
        return 2
    m = model.flat(cell.config, args.rehearse)
    backend = "interpret" if args.rehearse else "tpu"
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        row = reading(cell, m, seed, args.seconds, backend)
        rows.append(row)
        print(json.dumps(row), flush=True)
    prog = [r["program"] for r in rows if r["program"] is not None]
    ctrl = [r["control"] for r in rows if r["control"] is not None]
    print(json.dumps({"workload": args.workload,
                      "lower": max(prog) if prog else None,
                      "upper": min(ctrl) if ctrl else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
