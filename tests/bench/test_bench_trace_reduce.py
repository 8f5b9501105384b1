"""The reduction from a profiler trace to busy time, idle share and the
breakdown: on hand-made intervals, and on a small trace recorded here."""
import os

import pytest

from bench import trace_reduce as tr

TPU_TRACE = os.path.join(os.path.dirname(__file__), "data",
                         "tpu_tiny.xplane.pb")


def test_reduce_by_hand():
    ops = {"/device:TPU:0": [(1.0, 1.5, "fusion.1"), (1.4, 2.0, "fusion.1"),
                             (3.0, 3.5, "dot.2"), (9.0, 9.5, "copy")]}
    spans = [(0.0, 10.0, "arrival_wait"), (1.0, 4.0, "generate"),
             (2.0, 3.0, "decode_step"), (5.0, 6.0, "generate")]
    t = tr.reduce(ops, spans)
    assert t["window_s"] == pytest.approx(10.0)
    assert t["busy_s"] == pytest.approx(1.0 + 0.5 + 0.5)
    assert t["serving_s"] == pytest.approx(4.0)
    assert t["busy_in_serving_s"] == pytest.approx(1.5)
    assert t["device_ops"][0] == ["fusion.1", pytest.approx(1.1)]
    # the longest gap, 3.5-9.0, is named by the innermost host span in it
    assert t["idle_gaps"][0] == ["arrival_wait", pytest.approx(5.5)]
    assert ["decode_step", pytest.approx(1.0)] in t["idle_gaps"]
    assert t["chips_traced"] == 1


def test_several_chips_are_averaged():
    ops = {"a": [(0.0, 2.0, "x")], "b": [(0.0, 1.0, "x")]}
    t = tr.reduce(ops, [(0.0, 4.0, "generate")], chips=2)
    assert t["busy_s"] == pytest.approx(1.5)
    assert t["busy_in_serving_s"] == pytest.approx(1.5)


def test_no_host_span_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce({"a": [(0, 1, "x")]}, [])


def test_interval_helpers():
    assert tr.merge([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert tr.intersect([(0, 2), (3, 5)], [(1, 4)]) == [(1, 2), (3, 4)]
    assert tr.length([(0, 2), (3, 5)]) == 4


def test_recorded_trace_gives_the_host_spans(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("generate"):
        for _ in range(3):
            f(x).block_until_ready()
    with jax.profiler.TraceAnnotation("arrival_wait"):
        pass
    jax.profiler.stop_trace()
    ops, spans = tr.read_xplane(tr.find_xplane(str(tmp_path)),
                                ("generate", "arrival_wait"))
    assert sorted(n for _, _, n in spans) == ["arrival_wait", "generate"]
    assert all(e >= s for s, e, _ in spans)
    t = tr.reduce(ops, spans)
    assert t["serving_s"] > 0 and t["window_s"] >= t["serving_s"]


def test_trace_recorded_on_a_tpu():
    """Three runs of one jitted matmul+tanh inside a ``generate`` span, then
    an ``arrival_wait`` span, recorded on one TPU v5e."""
    ops, spans = tr.read_xplane(TPU_TRACE, ("generate", "arrival_wait"))
    assert list(ops) == ["/device:TPU:0"]
    names = [n for _, _, n in ops["/device:TPU:0"]]
    assert len(names) == 9 and "copy-start" in names
    assert all(" = " not in n for n in names)
    assert sorted(n for _, _, n in spans) == ["arrival_wait", "generate"]
    t = tr.reduce(ops, spans)
    assert t["chips_traced"] == 1
    assert 0 < t["busy_s"] < t["window_s"] < 0.1
    assert t["idle_gaps"] and t["device_ops"]
