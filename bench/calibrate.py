#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from, on the chip.

    python3 bench/calibrate.py --workload <name> --seconds <s> \
        --seeds <n> [<n> ...] --control <k>

For each seed, in one process: the timed path at the cell's own size and
load (the program's weights swapped in place, so the compiled programs are
the very ones a run uses), then the cell's comparison, which gives the
program's reading. On the first ``--control`` seeds it also reads the
control: the plain reference put in the program's place in the next lower
precision (float8 e4m3 matrix products for a bfloat16 model; bfloat16
arithmetic for the float32 grouping solver), judged by the same rule. One
line of JSON per seed goes to standard output. The benchmark's runs never
run this.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import device, spec, traffic  # noqa: E402
from bench.device import log  # noqa: E402


def serving(conf, mix, args):
    import jax.numpy as jnp
    from bench import serve_cell as sc
    from bench.reference import Reference, control_gaps
    server = sc.Server(conf, args.seeds[0])
    ref = Reference(conf["published"])
    ctl = Reference(conf["published"], dot_dtype=jnp.float8_e4m3fn)
    for n, seed in enumerate(args.seeds):
        if n:
            _idle(server)
            server.swap_weights(seed)
        win = sc.run_window(server, mix, seed, args.seconds, tail_cap_s=0.0)
        checks = sc.judge(conf, seed, win, ref=ref)
        row = {"seed": seed, "program": checks,
               "tokens_per_s": sc.end_to_end(win)}
        if n < args.control:
            by_index = {r.index: r for r in win.requests}
            picked = sc.sample(win, seed)
            t = time.monotonic()
            gaps = control_gaps(ref, ctl, seed,
                                [by_index[i].prompt for i in picked],
                                [win.records[i].tokens for i in picked],
                                conf["engine"]["max_seq"])
            row["control"] = {"max_gap": max(gaps), "per_request": gaps,
                              "seconds": time.monotonic() - t}
        print(json.dumps(row), flush=True)
    server.close()


def _idle(server, timeout_s=120):
    deadline = time.monotonic() + timeout_s
    while True:
        s = server.stats()
        if s and not s["running"] and not s["queue_depth"]:
            return
        if time.monotonic() > deadline:
            raise TimeoutError("the engine did not drain")
        time.sleep(0.1)


def quantizing(conf, mix, args):
    import numpy as np
    from bench import quantize_cell as qc
    from bench import solver_ref
    for n, seed in enumerate(args.seeds):
        qz = qc.Quantizer(conf, mix, seed)
        win = qc.run_window(qz, args.seconds)
        row = {"seed": seed, "program": qc.judge(conf, qz, win, seed),
               "weights_per_s": qc.end_to_end(win)}
        if n < args.control:
            w, _, _ = qc.sampled_blocks(qz, win, seed,
                                        mix["sample_blocks_per_step"])
            codes, scales = solver_ref.solve(w, precision="bfloat16")
            ex = solver_ref.excess(w, codes, scales)
            row["control"] = {"max_excess": float(np.max(ex)),
                              "median_excess": float(np.median(ex))}
        print(json.dumps(row), flush=True)
        del qz, win


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3)
    args = ap.parse_args()
    bench = spec.load_benchmark()
    cell = spec.cell(bench, args.workload)
    conf = spec.load_config(cell["config"])
    mix = traffic.load(cell["traffic"])
    device.use_checkout_cache()
    devs = device.accelerator(cell["chips"])
    log(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}")
    {"serve": serving, "quantize": quantizing}[mix["kind"]](conf, mix, args)


if __name__ == "__main__":
    main()
