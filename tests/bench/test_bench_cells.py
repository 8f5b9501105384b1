"""Every cell of BENCHMARK.json resolves to its files, and a cell added as
new files plus a ``workloads`` entry resolves and builds its schedule
without an edit to a file that is there."""
import json
import shutil

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
