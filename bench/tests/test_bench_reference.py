"""The plain float32 reference against the program's prefill-then-paged-
decode logits through ``ContinuousEngine``, on weights made from a seed, at
a small size on the CPU."""
import copy

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import weights as W
from bench.reference import Reference, served_gaps
from bench.serve_cell import build_model
from bench.tests.tiny import TINY, tied

SEED = 2 ** 35 + 11


def _f32(conf):
    conf = copy.deepcopy(conf)
    conf["program"]["overrides"]["dtype"] = jnp.float32
    return conf


def _program_logits(conf, prompts, n_new):
    """Every logits row the engine samples from: the prefill's last
    position, then one per decode step."""
    from repro.serve import ContinuousEngine
    model = build_model(conf)
    shapes = W.DenseShapes(conf["published"])
    params = W.packed_params(SEED, shapes, model.param_shapes(),
                             model.cfg.dtype)
    eng = ContinuousEngine(model, params, execution="packed",
                           **dict(conf["engine"], decode_horizon=1))
    rows = {}
    sample = eng._sample_and_advance

    def record(seq, logits):
        rows.setdefault(seq.req.req_id, []).append(np.asarray(logits))
        return sample(seq, logits)

    eng._sample_and_advance = record
    rids = [eng.submit(p, n_new) for p in prompts]
    out = eng.run()
    eng.close()
    return [np.stack(rows[r]) for r in rids], [out[r].tolist() for r in rids]


@pytest.mark.parametrize("variant", ["untied", "tied"])
def test_reference_matches_engine_logits(variant):
    conf = _f32(TINY if variant == "untied" else tied())
    vocab = conf["published"]["vocab_size"]
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, vocab, n).astype(np.int32) for n in (5, 19)]
    n_new = 6
    got, served = _program_logits(conf, prompts, n_new)
    ref = Reference(conf["published"])
    length = 32
    seqs = [list(p) + s[:-1] for p, s in zip(prompts, served)]
    tokens = np.zeros((len(seqs), length), np.int32)
    for i, s in enumerate(seqs):
        tokens[i, : len(s)] = s
    lo, hi = W.seed_words(SEED)
    logits = np.asarray(ref.logits(lo, hi, ref.hidden(SEED, tokens)))
    for i, p in enumerate(prompts):
        want = logits[i, len(p) - 1: len(p) - 1 + n_new]
        have = got[i][:, :vocab]
        assert have.shape == want.shape
        rel = np.linalg.norm(have - want) / np.linalg.norm(want)
        # both float32 on the same weights; only the order of sums differs
        assert rel < 1e-4, (variant, i, rel)
    gaps, n = served_gaps(ref, SEED, prompts, served, length)
    assert n == 2 * n_new
    assert max(gaps) < 1e-4


def test_weights_regenerate_per_layer():
    """The reference's per-layer regeneration gives the values the stacked
    program tree holds."""
    shapes = W.DenseShapes(TINY["published"])
    root = W.root_key(SEED)
    tree = W.program_tree(root, shapes)
    one = W.layer_weights(root, shapes, 1)
    codes, scales = one["mlp/wo"]
    q = tree["dec"]["s0"]["mlp"]["wo"]
    np.testing.assert_array_equal(np.asarray(q.codes[1]), np.asarray(codes))
    np.testing.assert_array_equal(np.asarray(q.scales[1]),
                                  np.asarray(scales))
    np.testing.assert_array_equal(np.asarray(tree["dec"]["s0"]["norm2"][1]),
                                  np.asarray(one["norm2"]))
    # the codec: equal-count levels, both signs, ascending codebooks
    lv = np.abs(np.asarray(codes)) - 1
    assert lv.min() == 0 and lv.max() == 7
    assert np.all(np.diff(np.asarray(scales), axis=-1) > 0)
    w = np.asarray(W.dequantize(codes, scales))
    assert 0.015 < w.std() < 0.025


def test_seed_words_cover_large_seeds():
    a = W.seed_words(2 ** 40 + 3)
    b = W.seed_words(3)
    assert int(a[0]) == int(b[0]) and int(a[1]) == 256 and int(b[1]) == 0
    ka, kb = W.root_key(2 ** 40 + 3), W.root_key(3)
    assert not np.array_equal(jax.random.key_data(ka),
                              jax.random.key_data(kb))
