"""A tiny configuration and mixes, for running the harness on the CPU."""
import copy

TINY = {
    "name": "tiny",
    "source": "test",
    "published": {
        "hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "vocab_size": 500, "rope_theta": 10000, "rms_norm_eps": 1e-05,
        "initializer_range": 0.02, "tie_word_embeddings": False},
    "reduced": [],
    "program": {"registry": "internlm2-1.8b",
                "overrides": {"d_model": 128, "d_ff": 256, "n_layers": 2,
                              "n_heads": 4, "n_kv_heads": 2,
                              "vocab_size": 500, "rope_theta": 10000.0,
                              "norm_eps": 1e-05}},
    "engine": {"max_batch": 4, "page_size": 8, "num_pages": 96,
               "max_seq": 128, "prefill_chunk": 32, "decode_horizon": 4,
               "prefix_cache": True},
    # between the readings at this size: the sound program 0.0-0.0005,
    # the float8 control 0.018-0.029
    "limits": {"max_gap": 0.005, "max_excess": 1e-6},
}

BATCH = {"kind": "serve", "arrival": {"process": "all_at_once"},
         "requests": 10,
         "prompt": {"dist": "lognormal", "median": 20, "sigma": 0.5,
                    "min": 4, "max": 48},
         "output": {"dist": "uniform", "min": 8, "max": 24},
         "window": "batch_decoding"}

CHAT = {"kind": "serve", "arrival": {"process": "poisson", "rate_per_s": 4.0},
        "tenants": {"n": 3, "prefix_len": 16, "zipf_s": 1.1},
        "prompt": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                   "min": 2, "max": 40},
        "output": {"dist": "lognormal", "median": 10, "sigma": 0.5,
                   "min": 4, "max": 20},
        "window": {"lead_in_s": 0.5}, "tail_s": 5.0}


def tied():
    conf = copy.deepcopy(TINY)
    conf["name"] = "tiny-tied"
    conf["published"].update(tie_word_embeddings=True,
                             embedding_multiplier=12.0,
                             attention_multiplier=0.0625)
    conf["program"]["registry"] = "granite-3-2b"
    conf["program"]["overrides"].update(embed_scale=12.0, query_scale=0.0625)
    return conf
