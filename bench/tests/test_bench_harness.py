"""The harness on the CPU: finding parts by name, the statistics taken over
a window, the traffic generator, and a run that finds no chip."""
import json
import math
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import spec, stats, traffic
from bench.client import Record
from bench.serve_cell import Window, attempted_failed, end_to_end
from bench.traffic import Request

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def test_cells_find_their_parts_by_name(bench):
    for w in bench["workloads"]:
        assert spec.cell(bench, w["name"]) is w
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        conf = spec.load_config(w["config"])
        mix = traffic.load(w["traffic"])
        assert mix["kind"] in ("serve", "quantize")
        assert conf["name"] == w["config"]
        for m in spec.per_layer(bench, w["name"]):
            assert callable(spec.load_metric(m["name"]).read)
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["reduced"] == spec.load_config(c["name"])["reduced"]


def test_unknown_names_fail(bench):
    with pytest.raises(KeyError):
        spec.cell(bench, "no-such-model.batch")
    with pytest.raises(FileNotFoundError):
        spec.load_config("no-such-model")
    with pytest.raises(FileNotFoundError):
        traffic.load("no-such-mix")
    with pytest.raises(FileNotFoundError):
        spec.load_metric("no_such_metric.batch")


def test_names_units_and_moves(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    names = [x["name"] for x in bench["workloads"] + bench["configs"]
             + bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
    for w in bench["workloads"]:
        have = {m["name"] for m in spec.end_to_end(bench, w["name"])}
        assert "setup_s" in have and len(have) >= 2
        layers = spec.per_layer(bench, w["name"])
        assert layers
        for m in layers:
            assert m["moves"] in have, (w["name"], m["name"])
    for m in bench["per_layer"]:
        mod = spec.load_metric(m["name"])
        assert (mod.UNIT, mod.SOURCE, mod.LAYER, mod.MOVES) == (
            m["unit"], m["source"], m["layer"], m["moves"])
        assert m["moves"] in e2e


def test_percentile_counts_failures_as_missing():
    xs = list(range(1, 20)) + [math.inf]          # 20 requests, 1 failed
    assert stats.percentile(xs, 95) == 19
    assert stats.percentile(xs + [math.inf], 95) == math.inf
    assert stats.percentile([5.0], 95) == 5.0
    assert stats.percentile([], 95) == math.inf


def _rec(i, due, events, counts, error=None, finish="length"):
    r = Record(i, due, sent=due)
    r.events, r.counts = list(events), list(counts)
    r.tokens = list(range(sum(counts)))
    r.error, r.finish = error, (None if error else finish)
    return r


def test_tokens_per_second_is_all_tokens_over_the_window():
    reqs = [Request(i, 0.0, np.zeros(4, np.int32), 10) for i in range(3)]
    recs = {0: _rec(0, 0, [0.5, 1.5, 2.5, 3.5], [8, 8, 8, 8]),
            1: _rec(1, 0, [1.51, 2.51, 3.52], [4, 4, 4]),
            2: _rec(2, 0, [], [], error="HTTP 500")}
    win = Window(reqs, recs, w0=1.0, w1=3.0, due_in_window=[],
                 counters={}, samples={}, lateness=[])
    out = end_to_end(win)
    # the window opens at w0 and closes with the first whole burst that
    # ends at or after w1 (3.5-3.52), which it counts: whole dispatches
    assert out["output_tokens_per_s"] == pytest.approx(36 / 2.52)
    assert "ttft_p95_ms" not in out
    assert attempted_failed(win) == (3, 1)
    # a stall at the end, with no burst after w1: the window closes at w1
    # and the stall lowers the rate
    for r in recs.values():
        keep = [k for k, t in enumerate(r.events) if t < 3.0]
        r.events = [r.events[k] for k in keep]
        r.counts = [r.counts[k] for k in keep]
    assert end_to_end(win)["output_tokens_per_s"] == pytest.approx(24 / 2.0)


def test_bursts_split_at_pauses_and_at_their_longest():
    arr = [(1.0, 8), (1.01, 8), (1.03, 8), (2.0, 8), (2.2, 8), (2.21, 8)]
    assert stats.bursts(arr, gap=0.05, longest=1.0) == [
        (1.0, 1.03, 24), (2.0, 2.0, 8), (2.2, 2.21, 16)]
    # a stream with no pauses is cut into pieces of the longest length
    stream = [(0.01 * i, 1) for i in range(100)]
    pieces = stats.bursts(stream, gap=0.05, longest=0.25)
    assert sum(n for _, _, n in pieces) == 100
    assert all(last - first < 0.25 for first, last, _ in pieces)
    assert len(pieces) == 4


def test_tails_over_every_request_due():
    n = 40
    reqs = [Request(i, 0.0, np.zeros(4, np.int32), 10) for i in range(n)]
    recs = {i: _rec(i, 10.0 + i, [10.0 + i + 0.1 + 0.01 * i,
                                  10.0 + i + 0.3], [1, 2])
            for i in range(n - 2)}
    recs[n - 2] = _rec(n - 2, 50.0, [], [], error="HTTP 503")
    win = Window(reqs, recs, w0=10.0, w1=60.0,
                 due_in_window=list(range(n)), counters={}, samples={},
                 lateness=[])
    out = end_to_end(win)
    # 38 answered (0.10..0.47 s), one refused and one never sent: the 95th
    # percentile of 40 is the 38th value, the slowest answered one
    assert out["ttft_p95_ms"] == pytest.approx((0.1 + 0.01 * 37) * 1e3)
    gaps = sorted([0.3 - 0.1 - 0.01 * i for i in range(n - 2)]
                  + [math.inf, math.inf])
    assert out["itl_p95_ms"] == pytest.approx(
        stats.percentile(gaps, 95) * 1e3)
    assert attempted_failed(win) == (n, 2)


CHAT = {"kind": "serve", "arrival": {"process": "poisson", "rate_per_s": 2.0},
        "tenants": {"n": 8, "prefix_len": 512, "zipf_s": 1.1},
        "prompt": {"dist": "lognormal", "median": 128, "sigma": 0.8,
                   "min": 8, "max": 1024},
        "output": {"dist": "lognormal", "median": 128, "sigma": 0.8,
                   "min": 8, "max": 512},
        "window": {"lead_in_s": 5.0}, "tail_s": 60.0}


def test_generator_is_a_function_of_the_seed():
    mix = CHAT
    a = traffic.generate(mix, 2 ** 40 + 7, 92544, 30, 32)
    b = traffic.generate(mix, 2 ** 40 + 7, 92544, 30, 32)
    c = traffic.generate(mix, 12345, 92544, 30, 32)
    assert [(r.due_s, r.max_tokens, r.prompt.tolist()) for r in a] == \
        [(r.due_s, r.max_tokens, r.prompt.tolist()) for r in b]
    assert [r.prompt.tolist() for r in a] != [r.prompt.tolist() for r in c]
    # another seed: the same sizes, the gaps in another order
    assert [r.max_tokens for r in a] == [r.max_tokens for r in c]
    ga = np.diff([r.due_s for r in a])
    gc = np.diff([r.due_s for r in c])
    assert not np.allclose(ga, gc)
    assert abs(ga.sum() - gc.sum()) < 5 / mix["arrival"]["rate_per_s"]
    rate = len(a) / (a[-1].due_s - a[0].due_s)
    assert rate == pytest.approx(mix["arrival"]["rate_per_s"], rel=0.05)
    assert all(r.prompt.max() < 92544 for r in a)
    # tenants: 512-token system prompts, the most popular first
    counts = np.bincount([r.tenant for r in a], minlength=8)
    assert counts[0] == counts.max() and counts[0] > 2 * counts[7]
    for r in a:
        assert len(r.prompt) >= 512 + mix["prompt"]["min"]


def test_batch_mix():
    mix = traffic.load("batch")
    reqs = traffic.generate(mix, 2 ** 33, 49155, 30, 32)
    other = traffic.generate(mix, 7, 49155, 30, 32)
    assert len(reqs) == 512 and all(r.due_s == 0 for r in reqs)
    # the same sizes in the same order for every seed; other token ids
    assert [(len(r.prompt), r.max_tokens) for r in reqs] == \
        [(len(r.prompt), r.max_tokens) for r in other]
    assert reqs[0].prompt.tolist() != other[0].prompt.tolist()
    # every batch of 32 spans the distribution
    for s in range(0, 512, 32):
        lens = sorted(len(r.prompt) for r in reqs[s:s + 32])
        outs = sorted(r.max_tokens for r in reqs[s:s + 32])
        assert lens[0] < 150 and lens[-1] > 500
        assert outs[0] < 300 and outs[-1] > 980
    lens = np.array([len(r.prompt) for r in reqs])
    assert 230 <= np.median(lens) <= 280 and lens.max() <= 1024
    outs = np.array([r.max_tokens for r in reqs])
    assert outs.min() >= 256 and outs.max() <= 1024


def _run(cwd, env_extra=None, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "internlm2-1.8b.batch", "--seed", "4294967311", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=timeout)


def test_no_accelerator_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "no accelerator" in p.stderr
    assert not p.stdout.strip()


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert not p.stdout.strip()


def test_benchmark_json_shape(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "bench/run.py"]
    assert 1 <= bench["run_seconds"] <= 51
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    assert len(json.dumps(bench)) < 64 * 1024
