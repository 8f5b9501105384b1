"""The reduction of the engine's spans and named programs: on hand-made
intervals, on a traced run of a reduced cell on the CPU, and on a trace of
the engine recorded on one TPU v5e."""
import os

import pytest

from bench import trace_program as tp

ENGINE_TRACE = os.path.join(os.path.dirname(__file__), "data",
                            "engine_tiny.xplane.pb")


def _request(t0, steps, step_s=1.0, rid=1):
    """Host spans of one request from ``t0``: lookup, prefill, insert, then
    a decode of ``steps`` steps of ``step_s`` each (dispatch then sample)."""
    meta = {"rid": rid}
    spans = [(t0 + 0.0, t0 + 0.1, "engine.lookup", meta),
             (t0 + 0.1, t0 + 1.0, "engine.prefill", meta),
             (t0 + 1.0, t0 + 1.1, "engine.insert", meta)]
    t = t0 + 1.1
    spans.append((t, t + 0.1, "engine.sample", meta))
    t += 0.1
    for _ in range(steps):
        spans.append((t, t + 0.2 * step_s, "engine.decode_step", meta))
        spans.append((t + 0.2 * step_s, t + step_s, "engine.sample", meta))
        t += step_s
    spans.append((t0 + 1.1, t, "engine.decode", meta))
    spans.append((t0, t, "engine.generate", meta))
    spans.append((t0, t, "generate", {}))
    return spans


def test_innermost_names_each_piece():
    spans = [(0, 10, "engine.generate"), (2, 4, "engine.prefill"),
             (4, 6, "engine.insert"), (7, 9, "engine.decode"),
             (7.5, 8, "engine.decode_step")]
    assert tp.innermost(spans) == [
        (0, 2, "engine.generate"), (2, 4, "engine.prefill"),
        (4, 6, "engine.insert"), (6, 7, "engine.generate"),
        (7, 7.5, "engine.decode"), (7.5, 8, "engine.decode_step"),
        (8, 9, "engine.decode"), (9, 10, "engine.generate")]


def test_gap_is_named_by_the_innermost_engine_span():
    spans = _request(0.0, steps=2)
    # the device runs the prefill from 0.2 to 0.9 and each step's program
    # for 0.5 s from 0.1 s into its step: the rest of serving is idle
    ops = {"tpu0": [(0.2, 0.9, "fusion.1"), (1.3, 1.8, "while.1"),
                    (2.3, 2.8, "while.1")]}
    mods = {"tpu0": [(0.2, 0.9, "jit_prefill_miss"),
                     (1.3, 1.8, "jit_decode_step"),
                     (2.3, 2.8, "jit_decode_step")]}
    t = tp.reduce(mods, ops, spans)
    idle = dict(t["idle_in_serving"])
    assert idle["engine.lookup"] == pytest.approx(0.1)
    assert idle["engine.prefill"] == pytest.approx(0.2)
    assert idle["engine.insert"] == pytest.approx(0.1)
    # decode_step spans (1.2-1.4, 2.2-2.4) lose 0.1 s each to the program
    assert idle["engine.decode_step"] == pytest.approx(0.2)
    # each sample: 0.1 before the first step, then 1.4-2.2 and 2.4-3.2
    # less the program's tail (0.4 s each)
    assert idle["engine.sample"] == pytest.approx(0.1 + 0.4 + 0.4)
    assert tp.NO_SPAN not in idle
    assert sum(idle.values()) == pytest.approx(3.2 - 0.7 - 1.0)
    assert t["programs"] == [["jit_decode_step", pytest.approx(1.0), 2],
                             ["jit_prefill_miss", pytest.approx(0.7), 1]]


def test_module_straddling_a_span_edge_counts_whole():
    """With the device's clock 0.3 s behind the host's, the first step's
    program starts before its span and before ``engine.decode``: it is
    still that step's, and its whole run counts as decode device time."""
    spans = _request(0.0, steps=3)
    lag = -0.3
    runs = [(1.2 + k + lag, 1.2 + k + lag + 0.5) for k in range(3)]
    mods = {"tpu0": [(s, e, "jit_decode_step") for s, e in runs]}
    d = tp.reduce(mods, {}, spans)["decode"]
    assert d["steps"] == 3
    assert d["span_s"] == pytest.approx(3.1)
    assert d["device_s"] == pytest.approx(1.5)
    assert d["device_ms_per_step"] == pytest.approx(500.0)
    assert d["overhead_ms_per_step"] == pytest.approx(1000 * 1.6 / 3)
    assert d["device_ms_per_step"] + d["overhead_ms_per_step"] == \
        pytest.approx(1000 * d["span_s"] / d["steps"])
    assert d["clock_lag_ms"]["min"] == pytest.approx(-300.0)
    assert d["clock_lag_ms"]["median"] == pytest.approx(-300.0)


def test_program_straddling_the_window_is_clipped():
    spans = [(1.0, 2.0, "generate", {}), (1.0, 2.0, "engine.generate", {})]
    mods = {"tpu0": [(0.5, 1.5, "jit_prefill_hit"),
                     (2.5, 3.0, "jit_prefill_hit")]}
    t = tp.reduce(mods, {}, spans)
    assert t["programs"] == [["jit_prefill_hit", pytest.approx(0.5), 1]]
    assert dict(t["idle_in_serving"]) == {
        "engine.generate": pytest.approx(0.5)}


def test_unmatched_counts_pair_each_step_with_the_nearest_run():
    spans = _request(0.0, steps=2)
    mods = {"tpu0": [(0.3, 0.4, "jit_decode_step"),     # a run with no step
                     (1.25, 1.75, "jit_decode_step"),
                     (2.25, 2.75, "jit_decode_step")]}
    d = tp.decode_split(mods, spans)
    assert d["steps"] == 2 and d["device_s"] == pytest.approx(1.0)
    assert d["clock_lag_ms"]["min"] == pytest.approx(50.0)


def test_two_chips_are_averaged():
    spans = _request(0.0, steps=2)
    a = [(1.3, 1.8, "jit_decode_step"), (2.3, 2.8, "jit_decode_step")]
    b = [(1.3, 1.6, "jit_decode_step"), (2.3, 2.6, "jit_decode_step")]
    t = tp.reduce({"tpu0": a, "tpu1": b}, {}, spans)
    assert t["chips_traced"] == 2
    assert t["programs"] == [["jit_decode_step", pytest.approx(0.8), 2]]
    d = t["decode"]
    assert d["device_s"] == pytest.approx(0.8)
    assert d["device_ms_per_step"] == pytest.approx(400.0)
    # chip 0 idles 2.2 s of serving's 3.2, chip 1 2.6 s
    assert sum(s for _, s in t["idle_in_serving"]) == pytest.approx(2.4)


def test_no_engine_span_and_no_decode():
    spans = [(0.0, 1.0, "generate", {}), (1.0, 2.0, "arrival_wait", {})]
    t = tp.reduce({"tpu0": [(0.5, 0.6, "jit__lambda")]},
                  {"tpu0": [(0.5, 0.6, "fusion.1")]}, spans)
    assert t["decode"] is None
    assert dict(t["idle_in_serving"]) == {tp.NO_SPAN: pytest.approx(0.9)}
    with pytest.raises(ValueError):
        tp.reduce({}, {}, [])


def test_module_name_drops_the_fingerprint():
    assert tp.module_name("jit_decode_step(1977554265506175217)") == \
        "jit_decode_step"
    assert tp.module_name("jit__lambda") == "jit__lambda"


def test_store_payload_ratio():
    evs = [{"kind": "store_bytes", "payload_bytes": 300,
            "charged_bytes": 100},
           {"kind": "store_bytes", "payload_bytes": 200,
            "charged_bytes": 100},
           {"kind": "store_bytes", "payload_bytes": 0, "charged_bytes": 0},
           {"kind": "transition", "payload_bytes": 9, "charged_bytes": 1}]
    assert tp.store_payload_ratio(evs) == pytest.approx(2.5)
    assert tp.store_payload_ratio([]) is None


def test_engine_trace_on_a_reduced_cell(monkeypatch, tmp_path):
    """``bench/engine_trace.py`` at its reduced size on the CPU: one
    recorder row per request served, engine spans in the kept trace."""
    import jax
    from bench import engine_trace as et
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    keep = tmp_path / "t.xplane.pb"
    try:
        out = et.traced_run(et.parse(
            ["--workload", "yi6b.agent", "--seed", str(2**32 + 11),
             "--seconds", "1", "--tiny", "--keep", str(keep)]),
            require_tpu=False)
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
    assert out["requests"] == out["rows"] > 0
    assert out["decode"]["steps"] == \
        out["requests"] * et.TINY_MIX["output_tokens"]
    assert 1.0 < out["store_payload_ratio"] < 128 / 32
    assert out["metrics"]["decode_ms_per_token"] > 0
    _, _, spans = tp.read_xplane(str(keep))
    rids = {s[3]["rid"] for s in spans if s[2] == "engine.generate"}
    assert len(rids) == out["requests"]


def test_engine_trace_recorded_on_a_tpu():
    """A reduced cell served on one TPU v5e with the engine's recorder
    attached (``python3 bench/engine_trace.py --workload yi6b.agent --seed
    7 --seconds 1 --tiny --keep ...``, cut down by
    ``tools/slim_xplane.py``): the programs are named, none is the
    anonymous ``jit__lambda``, and the paired decode runs are all the
    decode program's device time."""
    mods, ops, spans = tp.read_xplane(ENGINE_TRACE)
    assert list(mods) == ["/device:TPU:0"] and ops
    names = {n for _, _, n in mods["/device:TPU:0"]}
    assert {"jit_prefill_miss", "jit_decode_step"} <= names
    assert not any(n.startswith("jit__lambda") for n in names)
    t = tp.reduce(mods, ops, spans)
    assert t["programs"][0][0] in names
    d = t["decode"]
    runs = sum(n == "jit_decode_step" for _, _, n in mods["/device:TPU:0"])
    assert d["steps"] == runs > 0
    assert d["device_ms_per_step"] > 0 and d["overhead_ms_per_step"] > 0
    assert (d["device_ms_per_step"] + d["overhead_ms_per_step"]) \
        * d["steps"] == pytest.approx(1000.0 * d["span_s"])
    # The sum above holds by definition; the pairing is what shows the
    # split: every decode program run was paired with a step in a span.
    assert d["device_s"] == pytest.approx(dict(
        (n, secs) for n, secs, _ in t["programs"])["jit_decode_step"])
    idle = dict(t["idle_in_serving"])
    assert set(idle) <= {"engine.generate", "engine.lookup",
                         "engine.prefill", "engine.insert", "engine.decode",
                         "engine.decode_step", "engine.sample", tp.NO_SPAN}
    assert idle["engine.sample"] > 0
    rids = {s[3]["rid"] for s in spans if s[2] == "engine.generate"}
    assert len(rids) == sum(s[2] == "generate" for s in spans)
