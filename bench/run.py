#!/usr/bin/env python3
"""Run one benchmark cell on the chip this process finds.

    python3 bench/run.py --workload <config>.<traffic> --seed <n> \
        --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found by
name (``bench/spec.py``). Set-up builds the system from the seed, warms
every shape the cell uses and counts as ``setup_s``; the window then
measures for ``--seconds``; after it, what the timed path produced is
judged against the plain reference. With ``--trace 0`` the result holds the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics, read
from a profiler trace of part of the window and from the program's
counters.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each number compared beside its limit.
The checks are also the last lines of standard error. Without an
accelerator, or with fewer chips than the cell asks for, the run exits
non-zero and prints no result.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import device, spec, traffic  # noqa: E402
from bench.device import log  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def serve(conf, mix, args, devs, peaks):
    from bench import serve_cell as sc
    server = sc.Server(conf, args.seed)
    try:
        win = sc.run_window(server, mix, args.seed, args.seconds,
                            trace=bool(args.trace))
        dev = device.describe(devs)
    finally:
        server.close()
    del server
    setup_s = win.w0 - T_START
    sc.report_load(win)
    gc.collect()
    checks = sc.judge(conf, args.seed, win)
    metrics = sc.end_to_end(win)
    metrics["setup_s"] = setup_s
    attempted, failed = sc.attempted_failed(win)
    return dict(correct=sc.passed(checks), attempted=attempted,
                failed=failed, metrics=metrics, device=dev, checks=checks,
                ctx=sc.context(conf, peaks, win))


def quantize(conf, mix, args, devs, peaks):
    from bench import quantize_cell as qc
    qz = qc.Quantizer(conf, mix, args.seed)
    win = qc.run_window(qz, args.seconds, trace=bool(args.trace))
    setup_s = win.w0 - T_START
    log(f"window: {len(win.steps)} matrices, {win.weights} weights in "
        f"{win.w1 - win.w0:.3f} s")
    dev = device.describe(devs)
    checks = qc.judge(conf, qz, win, args.seed)
    metrics = qc.end_to_end(win)
    metrics["setup_s"] = setup_s
    failed = 0 if qc.passed(checks) else len(win.steps)
    return dict(correct=qc.passed(checks), attempted=len(win.steps),
                failed=failed, metrics=metrics, device=dev, checks=checks,
                ctx=qc.context(conf, peaks, win))


DRIVERS = {"serve": serve, "quantize": quantize}


def result_line(bench, name, out, trace):
    """The contract's JSON object for one run."""
    ctx = out["ctx"]
    if trace:
        metrics = {}
        for m in spec.per_layer(bench, name):
            value = spec.load_metric(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": out["metrics"][m["name"]],
                               "unit": m["unit"]}
                   for m in spec.end_to_end(bench, name)}
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics,
            "device": out["device"]}
    if trace and ctx.trace is not None:
        from bench import trace as tr
        line["device"]["busy_s"] = ctx.busy_seconds()
        line["device"]["window_s"] = ctx.trace_seconds
        line["breakdown"] = tr.breakdown(ctx.trace, ctx.trace_window)
    line["checks"] = out["checks"]
    return line


def main(argv=None):
    args = parse(argv)
    bench = spec.load_benchmark()
    cell = spec.cell(bench, args.workload)
    conf = spec.load_config(cell["config"])
    mix = traffic.load(cell["traffic"])
    for m in spec.per_layer(bench, args.workload):
        spec.load_metric(m["name"])           # an unknown reader fails now
    cache = device.use_checkout_cache()
    import jax
    devs = device.accelerator(cell["chips"])
    peaks = device.peaks(devs[0].device_kind)
    log(f"device: platform {devs[0].platform}, kind "
        f"{devs[0].device_kind}, count {len(devs)}; jax {jax.__version__}; "
        f"compile cache {cache}")
    log(f"cell {args.workload}: seed {args.seed}, {args.seconds} s, "
        f"trace {args.trace}")
    out = DRIVERS[mix["kind"]](conf, mix, args, devs, peaks)
    line = result_line(bench, args.workload, out, args.trace)
    for k, v in line["metrics"].items():
        log(f"metric {k}: {v['value']} {v['unit']}")
    for k, v in line["checks"].items():
        log(f"check {k}: {v['value']} (limit {v['limit']})")
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
