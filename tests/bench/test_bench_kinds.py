"""Kinds of model (``bench/kinds``): each configuration's kind gives what the
harness takes from it, with a reference beside it; the weights draw the
same bits as before kinds were split out; tensor ids never collide; and the
harness outside the kinds, references and configuration files names no
architecture."""
import hashlib
import re

import jax
import numpy as np
import pytest

from bench import cells, configs, kinds, weights

KIND_NAMES = sorted(p.stem for p in (cells.BENCH_DIR / "kinds").glob("*.py")
                    if p.stem != "__init__")
CONFIGS = [c["name"] for c in cells.load_benchmark()["configs"]] + ["tiny"]
INTERFACE = ("dims", "model_config", "program_params", "by_name",
             "prefill_flops", "decode_bytes", "weight_bytes",
             "decode_step_bytes", "payload_bytes", "charged_bytes_per_token")


@pytest.mark.parametrize("name", CONFIGS)
def test_a_configuration_takes_everything_from_its_kind(name):
    conf = configs.load(name)
    d = conf["dims"]
    kind = kinds.module(conf["kind"])
    assert d["kind"] == conf["kind"] and "reference" not in conf
    assert all(callable(getattr(kind, f)) for f in INTERFACE)
    hash(tuple(sorted(d.items())))           # a static jit argument
    cfg = configs.model_config(conf)
    assert (cfg.num_layers, cfg.vocab_size) == (d["layers"], d["vocab"])


@pytest.mark.parametrize("kind", KIND_NAMES)
def test_every_kind_has_a_reference(kind):
    import importlib
    kinds.module(kind)                       # declares its tensor ids
    ref = importlib.import_module(f"bench.references.{kind}")
    assert callable(ref.logits_at)


# sha256 over every leaf's path and bfloat16 bits, recorded from
# ``bench.weights.program_params`` before the dense code moved into its kind
DIMS = {"layers": 2, "d_model": 128, "heads": 4, "kv_heads": 2,
        "head_dim": 32, "d_ff": 256, "vocab": 512, "window": None,
        "rope_theta": 5e6, "norm_eps": 1e-5, "kind": "dense_gqa"}
RECORDED = {
    2**31 + 99:
        "c069811ae3b1bf6ff953a1597be8ac6c1d4c9670f31ccad15afecc1efbb276b1",
    7: "53c982e13d9b72e312919e8d594ab2974709d1ec2f65419858716d00c9db032c",
}


@pytest.mark.parametrize("seed", sorted(RECORDED))
def test_dense_weights_draw_the_recorded_bits(seed):
    params = kinds.module("dense_gqa").program_params(seed, DIMS)
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).view(np.uint16).tobytes())
    assert h.hexdigest() == RECORDED[seed]


@pytest.mark.parametrize("ids", [{"router": 5},           # id taken
                                 {"q": 13},               # name re-bound
                                 {"a_": 90, "b_": 90}])   # one id, two names
def test_a_colliding_tensor_id_is_an_error(ids):
    kinds.module("dense_gqa")
    table = dict(weights._IDS)
    with pytest.raises(ValueError):
        weights.declare(ids)
    assert weights._IDS == table
    assert weights.declare({"q": 5, "down": 12}) == {"q": 5, "down": 12}


def test_the_harness_names_no_architecture():
    words = ["num_key_value_heads", "intermediate_size", "rope_theta",
             "sliding_window", "hidden_size"] + KIND_NAMES
    pattern = re.compile("|".join(map(re.escape, words)))
    found = []
    for p in sorted(cells.BENCH_DIR.rglob("*")):
        rel = p.relative_to(cells.BENCH_DIR)
        if (not p.is_file() or "__pycache__" in rel.parts
                or rel.parts[0] in ("kinds", "references")
                or (rel.parts[0] == "configs" and p.suffix == ".json")):
            continue
        for n, line in enumerate(p.read_text().splitlines(), 1):
            if pattern.search(line):
                found.append(f"{rel}:{n}: {line.strip()}")
    assert not found, found
