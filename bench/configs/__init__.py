"""Configurations as run: ``<name>.json`` holds the published ``config``
(Hugging Face keys), the keys cut for one chip (``reduced``), what was
assumed, where the repository's own config departs from the published one,
the kind of model (``kind``: ``bench/kinds/<kind>.py`` reads the published
keys, builds the program's config and weights and counts the work;
``bench/references/<kind>.py`` checks it), and how it is served."""
import json
from pathlib import Path

from bench import kinds

CONFIG_DIR = Path(__file__).resolve().parent


def load(name: str) -> dict:
    with open(CONFIG_DIR / f"{name}.json") as f:
        c = json.load(f)
    c["name"] = name
    c["dims"] = dict(kinds.module(c["kind"]).dims(c["config"]),
                     kind=c["kind"])
    return c


def model_config(conf: dict):
    """The program's ``ModelConfig`` for a configuration as run."""
    return kinds.module(conf["dims"]["kind"]).model_config(conf)
