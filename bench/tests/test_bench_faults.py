"""A run with the timed path broken underneath must come out not correct,
and the lower-precision control must fail the comparison. The harness's
look for a chip is skipped; the rest of a run (set-up, the window over
HTTP, the judgement) is driven as ``bench/run.py`` drives it, at a small
size on the CPU."""
import copy

import jax.numpy as jnp
import numpy as np
import pytest

from bench import quantize_cell as qc
from bench import serve_cell as sc
from bench import solver_ref
from bench.reference import Reference, control_gaps
from bench.tests.tiny import BATCH, TINY

SEED = 2 ** 36 + 5
QUANTIZE = {"kind": "quantize", "bits": 4, "block": 64, "solver": "dp",
            "sample_blocks_per_step": 64}


def _conf(tag):
    """TINY with its own compiled programs (a distinct static model), so
    a broken program never leaks into another test's cache."""
    conf = copy.deepcopy(TINY)
    conf["program"]["overrides"]["decode_chunk"] = 1000 + tag
    return conf


def _serve(conf, breaker=None):
    server = sc.Server(conf, SEED)
    if breaker is not None:
        breaker(server)
    win = sc.run_window(server, BATCH, SEED, 2.0)
    server.close()
    return win, sc.judge(conf, SEED, win)


@pytest.fixture(scope="module")
def sound():
    conf = _conf(0)
    win, checks = _serve(conf)
    return conf, win, checks


def test_sound_run_is_correct(sound):
    _, _, checks = sound
    assert sc.passed(checks), checks
    assert checks["tokens_judged"]["value"] > 50


def test_token_altered_where_produced_is_caught():
    conf = _conf(1)
    vocab = conf["published"]["vocab_size"]

    def breaker(server):
        horizon = server.engine._horizon_fn

        def altered(*args):
            toks, valid, pools = horizon(*args)
            return (toks + 1) % vocab, valid, pools

        server.engine._horizon_fn = altered

    _, checks = _serve(conf, breaker)
    assert not sc.passed(checks), checks


def test_state_left_unchanged_is_caught(monkeypatch):
    """Every paged KV write returns the pools as they were."""
    import repro.models.layers as layers
    monkeypatch.setattr(layers, "paged_write",
                        lambda k_pool, v_pool, *a, **k: (k_pool, v_pool))
    monkeypatch.setattr(layers, "paged_write_packed",
                        lambda k_pool, v_pool, *a, **k: (k_pool, v_pool))
    _, checks = _serve(_conf(2))
    assert not sc.passed(checks), checks


def test_control_fails_the_comparison(sound):
    """The reference in float8 matrix products, in the program's place,
    reads a wider gap than the limit; the sound program reads less."""
    conf, win, checks = sound
    by_index = {r.index: r for r in win.requests}
    picked = sc.sample(win, SEED)
    ref = Reference(conf["published"])
    ctl = Reference(conf["published"], dot_dtype=jnp.float8_e4m3fn)
    gaps = control_gaps(ref, ctl, SEED,
                        [by_index[i].prompt for i in picked],
                        [win.records[i].tokens for i in picked],
                        conf["engine"]["max_seq"])
    limit = conf["limits"]["max_gap"]
    assert checks["max_gap"]["value"] < limit < max(gaps), (checks, gaps)


# -- the quantize cell ---------------------------------------------------------

@pytest.fixture(scope="module")
def quantizer():
    return qc.Quantizer(TINY, QUANTIZE, SEED)


def _quantize(qz, monkeypatch=None, wrap=None):
    if wrap is not None:
        import repro.core.quantize as q
        orig = q.quantize_blockwise
        monkeypatch.setattr(q, "quantize_blockwise",
                            lambda *a, **k: wrap(orig(*a, **k)))
    win = qc.run_window(qz, 0.5)
    return qc.judge(TINY, qz, win, SEED)


def test_sound_quantize_is_correct(quantizer):
    checks = _quantize(quantizer)
    assert qc.passed(checks), checks


def test_altered_answer_is_caught(quantizer, monkeypatch):
    import dataclasses

    def flip(q):
        codes = q.codes.reshape(-1, 64)
        codes = codes.at[::7, 3].set(-codes[::7, 3])
        return dataclasses.replace(q, codes=codes.reshape(q.codes.shape))

    assert not qc.passed(_quantize(quantizer, monkeypatch, flip))


def test_half_the_blocks_left_out_is_caught(quantizer, monkeypatch):
    import dataclasses

    def half(q):
        codes = q.codes.reshape(-1, 64)
        codes = codes.at[::2].set(0)
        return dataclasses.replace(q, codes=codes.reshape(q.codes.shape))

    assert not qc.passed(_quantize(quantizer, monkeypatch, half))


def test_quantize_control_fails_the_comparison():
    """The same exact solver in bfloat16 arithmetic misses the optimum by
    far more than the limit."""
    rng = np.random.default_rng(3)
    import ml_dtypes
    w = (0.02 * rng.standard_normal((512, 64))).astype(
        ml_dtypes.bfloat16).astype(np.float32)
    codes, scales = solver_ref.solve(w, precision="bfloat16")
    ex = solver_ref.excess(w, codes, scales)
    assert ex.max() > 1000 * TINY["limits"]["max_excess"]
    ok_codes, ok_scales = solver_ref.solve(w)
    assert solver_ref.excess(w, ok_codes, ok_scales).max() == 0.0


def test_open_loop_window_is_judged_too():
    """An open-loop mix (Poisson arrivals behind shared tenant prompts)
    through the same window: tails over the requests due in it, and the
    served tokens judged as a batch mix's are."""
    from bench.tests.tiny import CHAT
    conf = _conf(3)
    server = sc.Server(conf, SEED)
    win = sc.run_window(server, CHAT, SEED, 2.0)
    server.close()
    out = sc.end_to_end(win)
    assert win.due_in_window and 0 < out["ttft_p95_ms"] < sc.TAIL_CAP_S * 1e3
    assert out["itl_p95_ms"] > 0
    assert win.counters["prefix_positions_saved"] > 0
    assert sc.passed(sc.judge(conf, SEED, win))
