"""The configuration's sizes without importing JAX (the parent of a run
never does): the same mapping as ``benchmark.weights.dims``."""

HF_KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads",
           "num_key_value_heads", "head_dim", "intermediate_size",
           "vocab_size", "rope_theta", "rms_norm_eps")


def dims_of(config: dict) -> dict:
    missing = [k for k in HF_KEYS if k not in config]
    if missing:
        raise KeyError(f"configuration lacks {missing}")
    return {"E": config["hidden_size"], "L": config["num_hidden_layers"],
            "H": config["num_attention_heads"],
            "Hkv": config["num_key_value_heads"], "D": config["head_dim"],
            "M": config["intermediate_size"], "V": config["vocab_size"],
            "theta": float(config["rope_theta"]),
            "eps": float(config["rms_norm_eps"])}
