"""The control, at a size a test run can hold: the reference computed one
precision step below bfloat16 (fp8 e4m3 weights and activations), put in
place of the served tokens, goes through the run's own comparison and
comes out not correct, where the program's tokens on the same requests
pass."""
from bench.correct import control_tokens, served_gap


def test_control_fails_where_the_program_passes(tiny_run):
    result, checks, made = tiny_run(seed=2, seconds=3.0,
                                    judged=control_tokens)
    assert not result["correct"], checks
    ctrl = checks["logit_gap"]["value"]
    assert ctrl > checks["logit_gap"]["limit"]
    program, n = served_gap(made["conf"], 2, made["sample"],
                            made["prompt_of"], pad_to=128)
    assert n == sum(len(r.tokens) for r in made["sample"]) > 0
    assert program <= checks["logit_gap"]["limit"]
    assert ctrl >= 3 * program
