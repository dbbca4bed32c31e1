#!/usr/bin/env python3
"""Record the span test fixture on the chip, and time one span.

    python3 bench/span_probe.py --seed <n> [--parts cost,fixture]
        [--out artifacts/span_probe] [--tiny]
    python3 bench/span_probe.py --trim <recorded.xplane.pb> <dest.xplane.pb>

Not a benchmark cell.

- ``cost``: the time of one span (``repro.serve.spans``) with no trace
  running, and inside a trace.
- ``fixture``: a tiny engine (``bench/tests/tiny.py``) served over HTTP,
  traced over four engine steps once it has decoded (a packed prefill wave
  may fall among them), with the Python tracer off; the recording is
  written to ``<out>/tpu_spans.raw.xplane.pb``. ``--trim`` (on the CPU)
  makes the test fixture ``bench/tests/data/tpu_spans.xplane.pb`` of it.

Results go to ``<out>/span_probe.json`` and standard output. ``--tiny``
runs on whatever device there is, to rehearse the code path; its times are
not the chip's.

``--trim`` keeps of a recording what ``bench/trace.py`` and
``bench/spans.py`` read, so both read the same from either file: it drops
the ``/host:metadata`` plane (the programs' HLO), the device lines other
than ``XLA Modules`` and ``XLA Ops``, the device's unreferenced event
metadata, and the stats of device events and their metadata. It reads the
protocol buffer with TensorFlow's ``xplane_pb2``.
"""
import argparse
import copy
import json
import shutil
import sys
import tempfile
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spans as bs  # noqa: E402
from bench import traffic  # noqa: E402
from bench import trace as tr  # noqa: E402
from bench.device import log  # noqa: E402

FIXTURE_STEPS = 4


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int)
    ap.add_argument("--trim", nargs=2, metavar=("SRC", "DEST"))
    ap.add_argument("--parts", default="cost,fixture")
    ap.add_argument("--out", default=str(ROOT / "artifacts" / "span_probe"))
    ap.add_argument("--tiny", action="store_true")
    return ap.parse_args(argv)


# -- cost -----------------------------------------------------------------

def part_cost(args, out):
    from repro.serve.spans import PhaseClock
    clock = PhaseClock()

    def per_span(n):
        t = time.perf_counter_ns()
        for _ in range(n):
            with clock.span("engine.cost", kind="decode", rows=32):
                pass
        return (time.perf_counter_ns() - t) / n * 1e-3

    per_span(1000)
    off = [per_span(100_000) for _ in range(3)]
    import jax
    tmp = tempfile.mkdtemp(prefix="span-probe-")
    try:
        jax.profiler.start_trace(tmp)
        on = [per_span(10_000) for _ in range(3)]
        jax.profiler.stop_trace()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return {"us_per_span_off": off, "us_per_span_on": on}


# -- fixture ----------------------------------------------------------------

def part_fixture(args, out):
    """``FIXTURE_STEPS`` engine steps of the tiny engine once it has
    decoded, traced with the Python tracer and the programs' HLO off."""
    import jax
    from bench import serve_cell as sc
    from bench.client import LoadClient
    from bench.tests.tiny import BATCH, TINY
    conf = copy.deepcopy(TINY)
    mix = dict(BATCH, output={"dist": "uniform", "min": 32, "max": 48})
    server = sc.Server(conf, args.seed)
    client = None
    try:
        requests = traffic.generate(mix, args.seed,
                                    conf["published"]["vocab_size"], 2.0,
                                    conf["engine"]["max_batch"])
        client = LoadClient(server.host, server.port)
        client.start(requests, time.monotonic() + 0.05)
        deadline = time.monotonic() + 120
        while _snapshot(server)["decode_steps"] < 1:
            if time.monotonic() > deadline:
                raise TimeoutError("the tiny engine never decoded")
            time.sleep(0.001)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        n0 = _snapshot(server)["steps"]
        tmp = tempfile.mkdtemp(prefix="span-probe-")
        jax.profiler.start_trace(tmp, profiler_options=opts)
        while _snapshot(server)["steps"] < n0 + FIXTURE_STEPS and \
                time.monotonic() < deadline:
            time.sleep(0.01)
        jax.profiler.stop_trace()
        dest = Path(out) / "tpu_spans.raw.xplane.pb"
        shutil.copy(tr.find_xplane(tmp), dest)
        shutil.rmtree(tmp, ignore_errors=True)
        names = {}
        for s in bs.load_spans(str(dest)):
            names[s.name] = names.get(s.name, 0) + 1
        return {"bytes": dest.stat().st_size, "spans": names}
    finally:
        if client is not None:
            client.stop()
        server.close()


def trim(src: str, dest: str) -> int:
    """Write ``src`` to ``dest`` without what no reader reads (module
    docstring); the bytes written."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    space = xplane_pb2.XSpace()
    space.ParseFromString(Path(src).read_bytes())
    planes = [p for p in space.planes if p.name != "/host:metadata"]
    del space.planes[:]
    space.planes.extend(planes)
    for plane in space.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = [ln for ln in plane.lines
                 if ln.name in ("XLA Modules", "XLA Ops")]
        del plane.lines[:]
        plane.lines.extend(lines)
        used = {e.metadata_id for ln in plane.lines for e in ln.events}
        for k in [k for k in plane.event_metadata if k not in used]:
            del plane.event_metadata[k]
        for meta in plane.event_metadata.values():
            del meta.stats[:]
        for ln in plane.lines:
            for e in ln.events:
                del e.stats[:]
    data = space.SerializeToString()
    Path(dest).write_bytes(data)
    return len(data)


def check_trim(src: str, dest: str):
    """Both readers read the same from ``src`` and ``dest``."""
    if tr.load(src) != tr.load(dest):
        raise SystemExit("trim: the device events differ")
    if bs.load_spans(src) != bs.load_spans(dest):
        raise SystemExit("trim: the spans differ")


def _snapshot(server):
    """The engine's step counters, read from another thread."""
    for _ in range(100):
        try:
            st = server.engine.stats()
            return {"steps": st["steps"], "decode_steps": st["decode_steps"]}
        except RuntimeError:            # a dict resized by the engine thread
            time.sleep(0.001)
    raise RuntimeError("engine stats unreadable")


PARTS = {"cost": part_cost, "fixture": part_fixture}


def main(argv=None):
    args = parse(argv)
    if args.trim:
        n = trim(*args.trim)
        check_trim(*args.trim)
        print(f"{args.trim[1]}: {n} bytes")
        return
    if args.seed is None:
        sys.exit("--seed is required")
    from bench import device
    device.use_checkout_cache()
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.tiny:
        sys.exit(f"no TPU here ({dev.platform}); --tiny rehearses on it")
    Path(args.out).mkdir(parents=True, exist_ok=True)
    results = {"device": {"platform": dev.platform,
                          "kind": dev.device_kind,
                          "count": len(jax.devices())},
               "seed": args.seed}
    dest = Path(args.out) / "span_probe.json"
    for name in args.parts.split(","):
        t = time.monotonic()
        try:
            results[name] = PARTS[name](args, args.out)
        except Exception:               # noqa: BLE001
            results[name] = {"error": traceback.format_exc()}
        log(f"part {name}: {time.monotonic() - t:.1f} s")
        dest.write_text(json.dumps(results, indent=1, default=str))
    print(json.dumps(results, default=str), flush=True)


if __name__ == "__main__":
    main()
