"""A cell small enough for the CPU: the benchmark's own run at toy size."""
import copy

SPEC = {
    "name": "tiny", "arch": "dense", "registry": "paper_demo",
    "overrides": {"n_layers": 2, "d_model": 128, "n_heads": 4,
                  "n_kv_heads": 2, "head_dim": 32, "d_ff": 256,
                  "vocab_size": 512},
    "hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "vocab_size": 512, "rms_norm_eps": 1e-05, "rope_theta": 10000,
    "tie_word_embeddings": True,
}

TRAFFIC = {
    "generator": "sized", "loop": "closed", "clients": 4,
    "prompt_len": {"values": [16, 40], "weights": [0.5, 0.5]},
    "output_len": {"values": [6, 12], "weights": [0.5, 0.5]},
    "block": 2, "ramp_s": 0.5,
    "serve": {"replicas": 1, "max_batch": 4, "max_seq_len": 64,
              "page_size": 16},
    "check": {"min_tokens": 100000, "max_requests": 1000, "max_gap": 0.05},
}

CELL = {"name": "tiny.closed", "config": "tiny", "traffic": "tiny",
        "chips": 1}


def bench():
    return {
        "end_to_end": [{"name": n, "unit": u} for n, u in (
            ("ttft_p95_ms", "ms"), ("itl_p50_ms", "ms"),
            ("itl_p95_ms", "ms"), ("tokens_per_s", "tokens/s"),
            ("setup_s", "s"))],
        "per_layer": [],
    }


# agents behind a router of two replicas: four applications, each with
# its own 32-token system prompt and short tool-result tails
ROUTER = dict(copy.deepcopy(TRAFFIC), clients=8,
              prefix={"groups": 4, "len": 32},
              prompt_len={"values": [8, 16], "weights": [0.5, 0.5]},
              serve={"replicas": 2, "max_batch": 4, "max_seq_len": 64,
                     "page_size": 16})


def traffic(base=None, **kw):
    t = copy.deepcopy(base or TRAFFIC)
    t.update(kw)
    return t
