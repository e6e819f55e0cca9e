"""A tiny configuration and mix for driving the harness on the CPU."""
import copy

CONFIG = {
    "name": "tiny", "model": "qwen1.5-0.5b", "num_hidden_layers": 2,
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 4,
    "head_dim": 16, "intermediate_size": 128, "vocab_size": 512,
    "tie_word_embeddings": True, "hidden_act": "silu", "rope_theta": 1e6,
    "qkv_bias": True,
    "serving": {"codebook_entries": 16, "kv_bits": 0, "dtype": "float32",
                "n_slots": 4, "page_size": 16, "max_seq": 256,
                "prefill_chunk": 32},
    "correct": {"max_logit_gap": 0.05},
}

MIX = {
    "loop": "closed", "clients": 3, "per_client": 40, "warm_s": 1.0,
    "drain_s": 20, "trace_s": 1,
    "prompt": {"dist": "lognormal", "median": 48, "sigma": 0.8, "min": 32,
               "max": 128, "multiple": 32},
    "output": {"dist": "lognormal", "median": 16, "sigma": 0.8, "min": 8,
               "max": 64},
    "reference_requests": 3,
}

FOUR_CLIENT_MIX = dict(MIX, clients=4, per_client=30)


def config(k=16, kv_bits=0):
    c = copy.deepcopy(CONFIG)
    c["serving"].update(codebook_entries=k, kv_bits=kv_bits)
    return c


def program_config():
    """The program's own config of the tiny model."""
    from repro.configs import get_config, reduce_config
    return reduce_config(get_config(CONFIG["model"]))
