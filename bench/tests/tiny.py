"""A cell at a size a CPU test can hold: smollm-135m's block layout at
two layers of width 64, bfloat16, served through the Pallas kernel in
interpret mode."""
from __future__ import annotations

TINY_SIZES = {
    "num_layers": 2,
    "d_model": 64,
    "n_heads": 4,
    "n_kv_heads": 2,
    "head_dim": 16,
    "d_ff": 128,
    "vocab_size": 256,
    "dtype": "bfloat16",
}


def conf() -> dict:
    return {
        "name": "tiny",
        "registry": "smollm-135m",
        "overrides": dict(TINY_SIZES),
        "hidden_size": 64,
        "intermediate_size": 128,
        "num_attention_heads": 4,
        "num_hidden_layers": 2,
        "num_key_value_heads": 2,
        "rms_norm_eps": 1e-05,
        "rope_theta": 10000.0,
        "tie_word_embeddings": True,
        "vocab_size": 256,
        "torch_dtype": "bfloat16",
    }


def mix(kind="poisson", system=False) -> dict:
    m = {
        "name": "tiny",
        "kind": kind,
        "prompt": {"median": 12, "sigma": 0.5, "lo": 4, "hi": 24},
        "output": {"median": 6, "sigma": 0.5, "lo": 2, "hi": 16},
    }
    if kind == "backlog":
        m["count"] = 12
    if system:
        m["system_prompts"] = {"length": 16, "shares": [0.5, 0.5]}
        m["prompt"] = {"median": 6, "sigma": 0.5, "lo": 2, "hi": 12}
    return m


def cell(limit=0.5) -> dict:
    return {
        "name": "tiny.cell",
        "config": "tiny",
        "traffic": "tiny",
        "chips": 1,
        "max_batch": 4,
        "max_seq": 64,
        "block_size": 16,
        "rate_rps": 20.0,
        "drain_s": 20,
        "check_tokens": 32,
        "check_requests": 4,
        "limits": {"max_logit_gap": limit},
    }


PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
E2E = [
    {"name": "ttft_p90_ms", "unit": "ms"},
    {"name": "tpot_p90_ms", "unit": "ms"},
    {"name": "output_tokens_per_s", "unit": "tokens/s"},
    {"name": "setup_s", "unit": "s"},
]
