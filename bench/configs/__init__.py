"""Configurations as run: ``<name>.json`` holds the published ``config``
(Hugging Face keys), the keys cut for one chip (``reduced``), what was
assumed, where the repository's own config departs from the published one,
the plain reference that checks it, and how it is served."""
import json
from pathlib import Path

CONFIG_DIR = Path(__file__).resolve().parent


def load(name: str) -> dict:
    with open(CONFIG_DIR / f"{name}.json") as f:
        c = json.load(f)
    c["name"] = name
    c["dims"] = dims(c["config"])
    return c


def dims(hf: dict) -> dict:
    """The sizes the benchmark computes with, from the published keys."""
    heads = hf["num_attention_heads"]
    return {
        "layers": hf["num_hidden_layers"],
        "d_model": hf["hidden_size"],
        "heads": heads,
        "kv_heads": hf["num_key_value_heads"],
        "head_dim": hf.get("head_dim") or hf["hidden_size"] // heads,
        "d_ff": hf["intermediate_size"],
        "vocab": hf["vocab_size"],
        "window": hf.get("sliding_window"),
        "rope_theta": float(hf["rope_theta"]),
        "norm_eps": float(hf["rms_norm_eps"]),
    }


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration as run."""
    from repro.configs.base import ModelConfig
    d = conf["dims"]
    return ModelConfig(
        name=conf["name"], family="dense", num_layers=d["layers"],
        d_model=d["d_model"], num_heads=d["heads"],
        num_kv_heads=d["kv_heads"], head_dim=d["head_dim"], d_ff=d["d_ff"],
        vocab_size=d["vocab"], activation="silu", gated_mlp=True,
        rope_theta=d["rope_theta"], window_size=d["window"],
        norm_eps=d["norm_eps"], tie_embeddings=False,
        source=conf["source"])
