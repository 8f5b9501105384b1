"""Every cell of BENCHMARK.json resolves to its files; a cell added as new
files plus a ``workloads`` entry resolves and builds its schedule, and a
kind of model added as new files plus entries serves through ``run_cell``,
without an edit to a file that is there."""
import copy
import hashlib
import json
import shutil
import sys
from pathlib import Path

import pytest

from bench import cells, configs, schedule

BENCH = cells.load_benchmark()
NAMES = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", NAMES)
def test_cell_resolves_to_its_files(name):
    cell = cells.resolve(name)
    conf = configs.load(cell.config)
    mix = schedule.load_mix(cell.traffic)
    assert conf["dims"]["layers"] >= 1 and mix["turns"] >= 1
    assert cell.limits["logit_gap"] > 0
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(cells.reader(m["name"]))


def test_configs_match_their_files():
    for c in BENCH["configs"]:
        conf = configs.load(c["name"])
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert c["source"] == conf["source"]
        assert sorted(c["reduced"]) == sorted(conf["reduced"])


def test_per_layer_metrics_move_a_metric_their_cells_report():
    for m in BENCH["per_layer"]:
        for w in m.get("workloads", NAMES):
            assert m["moves"] in {e["name"] for e in cells.resolve(w).end_to_end}


def test_a_new_cell_is_files_and_an_entry(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(cells.BENCH_DIR, root / "bench")
    bench = json.loads(json.dumps(BENCH))
    mix = schedule.load_mix("agent")
    mix.update(first_prompt_tokens=[512, 1536], rate_req_per_s=1.0)
    del mix["name"]
    (root / "bench" / "traffic" / "agent_short.json").write_text(
        json.dumps(mix))
    (root / "bench" / "limits" / "yi6b.agent_short.json").write_text(
        json.dumps({"logit_gap": 1.0}))
    (root / "bench" / "metrics" / "turns_per_req.py").write_text(
        "def read(run):\n    return 1.0\n")
    bench["workloads"].append({"name": "yi6b.agent_short", "config": "yi-6b",
                               "traffic": "agent_short", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "turns_per_req", "unit": "1",
                               "better": "lower", "source": "program_counter",
                               "layer": "engine", "moves": "ttft_p90_s",
                               "workloads": ["yi6b.agent_short"]})
    bench["end_to_end"][0]["workloads"].append("yi6b.agent_short")
    cell = cells.resolve("yi6b.agent_short", bench, root / "bench")
    assert [m["name"] for m in cell.per_layer][-1] == "turns_per_req"
    assert cells.reader("turns_per_req", root / "bench")(None) == 1.0
    with open(root / "bench" / "traffic" / "agent_short.json") as f:
        new_mix = dict(json.load(f), name="agent_short")
    sessions = schedule.build(new_mix, 1, 64000)
    assert {s.prompt_lens[0] for s in sessions} == {512, 1536}
    assert (512, 0) in schedule.warm_shapes(new_mix)


def test_unknown_cell_or_missing_file_is_an_error(tmp_path):
    with pytest.raises(KeyError):
        cells.resolve("no.such.cell")
    shutil.copytree(cells.BENCH_DIR, tmp_path / "bench")
    (tmp_path / "bench" / "limits" / "yi6b.agent.json").unlink()
    with pytest.raises(FileNotFoundError):
        cells.resolve("yi6b.agent", BENCH, tmp_path / "bench")


NEW_KIND = Path(__file__).resolve().parent / "data" / "new_kind"


def _hashes(top: Path) -> dict:
    return {str(p.relative_to(top)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(top.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_a_new_kind_is_files_and_an_entry(tmp_path, monkeypatch):
    """A squared-ReLU MLP without a gate, as files alone: its kind, its
    reference, a configuration, a traffic mix and a limits file under a copy
    of ``bench/``, and entries in the copy's ``BENCHMARK.json``. The copy's
    harness serves it through ``run_cell`` on the CPU and finds it
    correct."""
    import jax
    root = tmp_path / "checkout"
    shutil.copytree(cells.BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(NEW_KIND, root / "bench", dirs_exist_ok=True)
    before, after = _hashes(cells.BENCH_DIR), _hashes(root / "bench")
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == set(_hashes(NEW_KIND))
    bench = copy.deepcopy(BENCH)
    bench["configs"].append({"name": "tiny-relu2", "source": "test",
                             "file": "bench/configs/tiny-relu2.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-relu2.chat",
                               "config": "tiny-relu2", "traffic": "tiny_chat",
                               "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("tiny-relu2.chat")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    monkeypatch.syspath_prepend(str(root))
    ours = {k: m for k, m in sys.modules.items()
            if k == "bench" or k.startswith("bench.")}
    for k in ours:
        del sys.modules[k]
    try:
        from bench import configs as c2, kinds as k2, run as r2, work as w2
        assert Path(r2.__file__).is_relative_to(root)
        conf = c2.load("tiny-relu2")
        d = conf["dims"]
        assert d["kind"] == "relu2_dense"
        params = k2.module(d["kind"]).program_params(1, d)
        assert set(params["layers"]["mlp"]) == {"w_up", "w_down"}
        assert c2.model_config(conf).activation == "relu2"
        per_layer = 2 * 128 * 128 + 2 * 128 * 64 + 2 * 128 * 512
        assert w2.layer_matmul_params(d) == per_layer
        # 2 layers, 3 new tokens attending 1, 2 and 3 keys, and the head
        assert w2.prefill_flops(d, 3, 0) == 2 * (
            2 * 3 * per_layer + 4 * 4 * 32 * (1 + 2 + 3)) + 2 * 128 * 512
        result, checks, made = r2.run_cell(
            r2.parse(["--workload", "tiny-relu2.chat", "--seed",
                      str(2**31 + 5), "--seconds", "2", "--trace", "0"]),
            require_tpu=False)
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        for k in [k for k in sys.modules
                  if k == "bench" or k.startswith("bench.")]:
            del sys.modules[k]
        sys.modules.update(ours)
    assert result["correct"], checks
    assert made["conf"]["name"] == "tiny-relu2"
    assert result["attempted"] > 5 and result["failed"] == 0
    assert checks["logit_gap"]["value"] <= checks["logit_gap"]["limit"]
