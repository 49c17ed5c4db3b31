"""Toy sizes of the benchmark's configurations and traffic, for the tests."""

import json
import os

import spec

# toy sizes of a dense configuration, and a mix of short requests
TINY = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "vocab_size": 300}
MIX = {"prompt": {"dist": "lognormal", "median": 20, "sigma": 0.6,
                  "min": 4, "max": 40},
       "output": {"dist": "lognormal", "median": 24, "sigma": 0.3,
                  "min": 16, "max": 32}}
ENGINE = {"slots": 4, "max_len": 80, "page_size": 8, "prefill_chunk": 16}


def tiny_config(config: str) -> dict:
    """The configuration file ``config`` at toy sizes, its heads grouped as
    the original groups them (MHA stays MHA)."""
    with open(os.path.join(spec.BENCH, "configs", f"{config}.json")) as f:
        cfg = json.load(f)
    mha = cfg["num_key_value_heads"] == cfg["num_attention_heads"]
    cfg.update(TINY, num_key_value_heads=4 if mha else 2)
    # interpret-mode kernels compile to no tpu_custom_call
    cfg["bench"]["kernels"] = {"decode": [], "chunk": []}
    return cfg
