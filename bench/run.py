"""Run one cell of BENCHMARK.json on the chip and print its result line.

    python -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one chip. Set-up (``setup_s``) runs from process start to
the window: the compile cache is placed inside the checkout (or at
``$JAX_COMPILATION_CACHE_DIR``), the weights are drawn on the device from
the seed, the ``ServeEngine`` is built with the compiled Pallas kernel,
and every shape the cell's traffic can produce is warmed. The window
then offers the cell's open-loop traffic for ``--seconds`` through
``decode.generate_stream`` stubs. After it, open streams are drained,
peak memory is read, the engine is freed and a sample of what was served
is compared with the float32 reference.

With ``--trace 0`` the line carries the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler, with the program's own
spans on (``bench.trace.capture``), and the line carries the per-layer
metrics, the device's busy and window seconds and a breakdown whose idle
gaps are labelled by the program span over most of each
(``bench.spans``). The numbers compared for ``correct`` come last, on
stderr and under ``compared`` in the line.

``--rehearse`` is the CPU rehearsal for the tests: smoke sizes from the
configuration's ``rehearsal`` group and interpret-mode kernels. Without
it, a machine whose JAX finds no TPU, or fewer chips than the cell asks
for, gets exit code 2 and no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

# libtpu writes its logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from . import check, model, serve, spans, spec, traffic, work  # noqa: E402
from .client import OpenLoop  # noqa: E402
from .measure import Run  # noqa: E402

EXIT_NO_CHIP = 2


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at smoke sizes, interpret-mode "
                         "kernels; never a fallback")
    return ap.parse_args(argv)


def op_name(hlo: str) -> str:
    """``%fusion.12 = (bf16[...]) fusion(...)`` -> ``fusion``: an op's
    name without its HLO text and instance number."""
    name = hlo.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"(\.\d+|\.remat\d*)+$", "", name)


def client_gaps(t) -> list:
    """The trace's ten longest device-idle gaps, [label, seconds], each
    labelled by the benchmark's host span that covers most of it."""
    from . import trace as tr

    # the client drains all the time; a dispatch or a retry wait inside
    # a gap says more about it
    rank = {"bench.dispatch": 3, "bench.retry_wait": 2, "bench.drain": 1}
    client = [sp for sp in t.spans if sp[0] in rank]
    out = []
    for lo, hi in sorted(tr.gaps(t.ops, t.window),
                         key=lambda g: g[0] - g[1])[:10]:
        cover = {}
        for name, s, e in client:
            ov = min(e, hi) - max(s, lo)
            if ov > 0:
                cover[name] = cover.get(name, 0.0) + ov
        label = max(cover, key=lambda n: (rank[n], cover[n])) if cover \
            else "unattributed"
        out.append([label, (hi - lo) / 1e9])
    return out


def breakdown(run) -> dict:
    """Top device ops by time, and the longest device-idle gaps labelled
    by the program span that covers most of each, else by the client's."""
    t = run.trace
    totals = {}
    for name, s, e in t.in_window(t.ops):
        name = op_name(name)
        totals[name] = totals.get(name, 0.0) + (e - s) / 1e9
    ops = sorted(totals.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[n, v] for n, v in ops],
            "idle_gaps": spans.label_gaps(t, t.program, client_gaps(t))}


def main(argv=None) -> int:
    return execute(argv)[0]


def execute(argv=None):
    """(exit code, the ``Run`` its metrics were read from; None where the
    run printed no result)."""
    args = parse(argv)
    if args.rehearse and args.trace:
        print("bench: the CPU rehearsal takes no trace", file=sys.stderr)
        return EXIT_NO_CHIP, None
    cell = spec.cell(args.workload)
    device, cache, err = serve.start(cell.chips, args.rehearse)
    if err:
        print(f"bench: {err}", file=sys.stderr)
        return EXIT_NO_CHIP, None
    import jax

    from .clock import CompileClock, GcClock

    clock = CompileClock()
    m = model.flat(cell.config, args.rehearse)
    mix = cell.traffic
    t_ready = time.perf_counter()
    eng, stubs, phases = serve.prepare(
        m, mix, args.seed, "interpret" if args.rehearse else "tpu")
    reqs = traffic.schedule(mix, args.seed, args.seconds, m["vocab_size"],
                            prompt_div=m["prompt_divisor"],
                            output_div=m["output_divisor"])
    c0 = clock.mark()
    e0 = (eng.oob_events, eng.shed_admits, eng.stream_steps)
    loop = OpenLoop(stubs, reqs, trace=bool(args.trace))
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace \
        else None
    setup_s = time.perf_counter() - T_START
    gcc = GcClock()
    gcc.start()
    if args.trace:
        from . import trace as tr

        profiled = tr.capture(trace_dir)
    else:
        profiled = contextlib.nullcontext()
    with profiled:
        loop.run(args.seconds)
        gcc.stop()
        in_window = clock.since(c0)
        # drained before the profiler stops: stopping it holds this
        # thread for seconds, and a stream left undrained that long fills
        # its connection's reply heap
        loop.finish()
    oob = eng.oob_events - e0[0]
    print(f"compile cache: {cache}")
    print(f"set-up: start to device {t_ready - T_START:.3f}s, "
          + ", ".join(f"{k} {v:.3f}s" for k, v in phases.items())
          + f"; compiles {c0['compiles']} ({c0['compile_s']:.3f}s), "
          f"cache reads {c0['cache_hits']}, lowerings {c0['lowerings']}")
    print(f"compiles in window: {in_window['lowerings']} lowerings, "
          f"{in_window['compiles']} XLA compiles, "
          f"{in_window['cache_hits']} cache reads")
    late = [r.late_s for r in loop.records if r.late_s is not None]
    late_ms = sorted(1e3 * x for x in late)
    print(f"generator: {len(reqs)} requests scheduled, {len(late)} "
          f"came due; lateness ms p50 "
          f"{late_ms[len(late_ms) // 2] if late_ms else 0:.3f} max "
          f"{late_ms[-1] if late_ms else 0:.3f}; {loop.sheds} sheds "
          f"(engine: {eng.shed_admits - e0[1]}), "
          f"{eng.stream_steps - e0[2]} decode steps")
    print(f"client: longest pass of its loop {1e3 * loop.longest_pass_s:.3f} "
          f"ms; {gcc.line()}")

    stats = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    serve.release(eng, loop)
    del eng, stubs

    # -- correctness -----------------------------------------------------
    picked = check.sample(loop.records, args.seed)
    length = (m["max_pages_per_seq"] * m["page_tokens"] if args.rehearse
              else mix["max_total_tokens"])
    failed = sum(1 for r in loop.records if r.error is not None)
    lost = sum(r.req.max_new - len(r.tokens) for r in loop.records
               if r.done)
    values = {"lost_tokens": float(lost), "failed_requests": float(failed),
              "oob_events": float(oob)}
    if picked:
        t_ref = time.perf_counter()
        g = check.gaps(m, args.seed, picked, length)
        values["max_logit_gap"] = float(g.max())
        served = sum(len(r.tokens) for r in {id(r): r for r in picked}
                     .values())
        print(f"check: {len(set(map(id, picked)))} requests, {served} "
              f"served tokens against the float32 reference in "
              f"{time.perf_counter() - t_ref:.3f}s")
    else:
        print("check: no finished request to compare")
    limits = cell.limits["rehearsal" if args.rehearse else "chip"]
    cmp = check.compared(values, limits)
    correct = bool(picked) and all(c["ok"] for c in cmp.values())

    # -- metrics ---------------------------------------------------------
    run = Run(m=m, records=loop.records, t0=loop.t0, end=loop.end,
              setup_s=setup_s)
    metrics = {}
    if args.trace:
        from . import trace as tr

        t_load = time.perf_counter()
        run.trace = tr.load(trace_dir)
        print(f"trace: {len(run.trace.ops)} device ops, "
              f"{len(run.trace.program)} program spans read in "
              f"{time.perf_counter() - t_load:.3f}s")
        print(spans.idle_line(spans.idle_by_span(run.trace,
                                                 run.trace.program),
                              run.trace.window_s))
        shutil.rmtree(trace_dir, ignore_errors=True)
        run.peaks = work.peaks(device["kind"])
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s
        wanted = cell.per_layer
    else:
        wanted = cell.end_to_end
    for mt in wanted:
        v = spec.reader(mt["name"]).read(run)
        if v is not None:
            metrics[mt["name"]] = {"value": float(v), "unit": mt["unit"]}
    print(f"pool: at most {run.peak_pages()} of {m['pool_pages']} pages "
          f"held by admitted streams at once")
    for note in run.notes:
        print(note)
    print(f"run: {time.perf_counter() - T_START:.3f}s from process start")
    result = {"correct": correct, "attempted": len(run.due()),
              "failed": failed, "metrics": metrics, "device": device}
    if args.trace:
        result["breakdown"] = breakdown(run)
    if args.rehearse:
        result["rehearsal"] = True
    result["compared"] = {k: {"value": c["value"], "limit": c["limit"]}
                          for k, c in cmp.items()}
    sys.stdout.flush()
    for k, c in cmp.items():
        print(f"compared {k}: {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['ok'] else 'FAIL'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0, run


if __name__ == "__main__":
    sys.exit(main())
