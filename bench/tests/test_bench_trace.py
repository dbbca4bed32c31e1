"""Trace reduction and operation counts, against a small trace recorded on a
TPU v5e and against counts worked out by hand."""
from pathlib import Path

import pytest

from bench import flops, trace as tr
from bench.context import Context
from bench.device import PEAKS, peaks
from bench.spec import load_config, load_metric

FIXTURE = str(Path(__file__).parent / "data" / "tpu_fixture.xplane.pb")
V5E = PEAKS["TPU v5 lite"]

# one decode-shape kernel call as the v5e trace printed it
DECODE_CALL = (
    "%msb_matmul.1 = f32[32,8192]{1,0:T(8,128)S(1)} custom-call(bf16[32,2048]"
    "{1,0:T(8,128)(2,1)} %x.1, u8[2048,4096]{1,0:T(8,128)(4,1)} %constant.2, "
    "f32[128,8,2048]{2,1,0:T(8,128)} %constant.3), custom_call_target="
    "\"tpu_custom_call\", operand_layout_constraints={bf16[32,2048]{1,0}, "
    "u8[2048,4096]{1,0}, f32[128,8,2048]{2,1,0}}")


@pytest.fixture(scope="module")
def fixture_trace():
    devs, span = tr.load(FIXTURE)
    return devs[0], span[1] - span[0]


def test_union_counts_overlap_once():
    assert tr.union_length([(0, 10), (5, 15), (20, 25)]) == 20
    assert tr.union_length([(3, 4), (0, 10)]) == 10
    assert tr.union_length([]) == 0


def test_busy_and_gaps_clip_to_window():
    ev = [tr.Event("a", 0, 10), tr.Event("b", 5, 10), tr.Event("c", 30, 40)]
    assert tr.busy_ns(ev, (5, 50)) == 10 + 20
    assert tr.idle_gaps(ev, (0, 100)) == [(15, 15), (70, 30)]


def test_self_time_of_nested_ops():
    ev = [tr.Event("%while.1 = ()", 0, 100), tr.Event("%while.2 = ()", 10, 50),
          tr.Event("%fusion.1 = ()", 20, 10), tr.Event("%fusion.2 = ()", 70, 20),
          tr.Event("%copy.3 = ()", 120, 5)]
    assert tr.self_ns(ev) == [100 - 50 - 20, 50 - 10, 10, 20, 5]


def test_host_window_on_the_trace_clock():
    span = (1_792_333_594_000_000_000, 1_792_333_594_300_000_000)
    # the host measured from 20 ms to 290 ms after the profile started
    t0 = span[0]
    assert tr.host_window(span, t0 + 20_000_000, t0 + 290_000_001) == (
        20e6, 290000001.0)
    # never outside the profile
    assert tr.host_window(span, t0 - 1, t0 + 400_000_000) == (0.0, 300e6)
    # a file that does not say when it started: the host's own length
    assert tr.host_window(None, 5, 105) == (0.0, 100.0)


def test_names():
    assert tr.op_short(DECODE_CALL) == "msb_matmul"
    assert tr.op_short("%fusion.52 = s32[8]{0} fusion(...)") == "fusion"
    assert tr.op_short("%copy-start.7 = (f32[2]) copy-start(...)") == \
        "copy-start"
    assert tr.module_short("jit__paged_horizon_step(123)") == \
        "jit__paged_horizon_step"


def test_recorded_trace(fixture_trace):
    dev, window = fixture_trace
    assert dev.plane == "/device:TPU:0"
    assert window == 284365001.0
    assert len(dev.modules) == 6 and len(dev.ops) == 15
    kernel = [o for o in dev.ops if tr.op_short(o.name) == "msb_matmul"]
    assert len(kernel) == 3
    busy = tr.busy_ns(dev.ops, (0, window))
    # the ops never overlap in this trace except the async copy pair
    assert 0 < busy <= sum(o.dur for o in dev.ops)
    gaps = tr.idle_gaps(dev.ops, (0, window))
    assert sum(g for _, g in gaps) == pytest.approx(window - busy)
    bd = tr.breakdown(dev, (0, window))
    assert bd["device_ops"][0][0] == "jit__lambda:msb_matmul"
    assert bd["device_ops"][0][1] == pytest.approx(
        sum(o.dur for o in kernel) * 1e-9)
    assert len(bd["idle_gaps"]) == 10
    assert all(g[0].startswith("host, unattributed") for g in bd["idle_gaps"])


def test_kernel_cost_from_recorded_call(fixture_trace):
    dev, _ = fixture_trace
    call = next(o for o in dev.ops if tr.op_short(o.name) == "msb_matmul")
    f, b = tr.msb_call_cost(call.name)
    # x (8, 256) bf16 @ codes (256, 512/2) u8 with codebooks (8, 8, 256) f32
    assert f == 2 * 8 * 256 * 512
    assert b == 8 * 512 * 4 + 8 * 256 * 2 + 256 * 256 + 8 * 8 * 256 * 4


def test_kernel_cost_at_decode_shape():
    f, b = tr.msb_call_cost(DECODE_CALL)
    assert f == 2 * 32 * 2048 * 8192 == 1_073_741_824
    # result f32, activations bf16, codes 4-bit, one f32 codebook per 64
    assert b == (32 * 8192 * 4 + 32 * 2048 * 2 + 2048 * 8192 // 2
                 + 2048 * 8192 // 64 * 8 * 4) == 17_956_864
    t, bound = tr.roofline_seconds(f, b, V5E)
    assert bound == "memory" and t == pytest.approx(b / 819e9)


def test_flops_per_token_by_hand():
    il = load_config("internlm2-1.8b")["published"]
    gr = load_config("granite-3-2b")["published"]
    # 24 x 2 x (2048*2048 + 2*2048*1024 + 2048*2048 + 3*2048*8192)
    # + 2 x 2048 x 92544
    assert flops.matmul_flops_per_token(il) == 3_398_959_104
    # 40 x 2 x (2048*2048 + 2*2048*512 + 2048*2048 + 3*2048*8192)
    # + 2 x 2048 x 49155
    assert flops.matmul_flops_per_token(gr) == 5_066_731_520
    assert flops.attention_flops(il, 100) == 24 * 4 * 16 * 128 * 100
    assert flops.attention_flops(gr, 100) == 40 * 4 * 32 * 64 * 100
    for start, stop in [(0, 1), (0, 300), (17, 18), (250, 1024)]:
        assert flops.span_flops(il, start, stop) == pytest.approx(
            flops.token_flops(il, range(start, stop)))
    assert flops.span_flops(il, 5, 5) == 0


def test_peaks_table():
    assert peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks("TPU v9 imaginary")


def test_readers_on_recorded_trace(fixture_trace):
    dev, window = fixture_trace
    ctx = Context({}, {"decode_horizon": 8, "max_batch": 32}, V5E,
                  trace=dev, trace_window=(0.0, window))
    busy = tr.busy_ns(dev.ops, (0, window))
    idle = load_metric("idle_share.batch").read(ctx)
    assert idle == pytest.approx(100 * (1 - busy / window))
    roof = load_metric("msb_matmul_roofline.batch").read(ctx)
    kernel = [o for o in dev.ops if tr.op_short(o.name) == "msb_matmul"]
    least = sum(tr.roofline_seconds(*tr.msb_call_cost(o.name), V5E)[0]
                for o in kernel)
    assert roof == pytest.approx(100 * least / (sum(o.dur for o in kernel)
                                                * 1e-9))
    assert 0 < roof < 100
    # nothing of these programs in this trace: the readers say so
    assert load_metric("decode_step_ms.batch").read(ctx) is None
    assert load_metric("solve_us_per_block.quantize").read(ctx) is None
    assert load_metric("mfu.batch").read(ctx) is None
    ctx.counters.update(burst_flops=1e9, burst_seconds=0.25)
    assert load_metric("mfu.batch").read(ctx) == pytest.approx(
        100 * 1e9 / (0.25 * 197e12))


def test_module_readers():
    h = [tr.Event("jit__paged_horizon_step(1)", 0, 8e6),
         tr.Event("jit__paged_prefill_packed(2)", 9e6, 2e6),
         tr.Event("jit__paged_horizon_step(1)", 12e6, 10e6),
         tr.Event("jit__solve_chunked(3)", 30e6, 5e6)]
    dev = tr.DeviceTrace("/device:TPU:0", h, h)
    ctx = Context({}, {"decode_horizon": 8, "max_batch": 32}, V5E,
                  trace=dev, trace_window=(0.0, 40e6),
                  counters={"blocks_in_trace": 1000})
    assert load_metric("decode_step_ms.batch").read(ctx) is None
    # two dispatches that retired 8 tokens in each of 32 rows: 9 ms each,
    # 8 steps each
    ctx.counters.update(trace_decode_tokens=2 * 256,
                        trace_decode_dispatches=2)
    assert load_metric("decode_step_ms.batch").read(ctx) == 18.0 / 16
    # the same dispatches retiring half as much take twice as long a step
    ctx.counters["trace_decode_tokens"] = 2 * 128
    assert load_metric("decode_step_ms.batch").read(ctx) == 18.0 / 8
    assert load_metric("solve_us_per_block.quantize").read(ctx) == 5.0
    assert load_metric("idle_share.quantize").read(ctx) == pytest.approx(
        100 * (1 - 25 / 40))
