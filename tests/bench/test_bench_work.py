"""bench/work.py's operation and byte counts against counts made by hand."""
from bench import configs, work

YI = configs.load("yi-6b")["dims"]
DANUBE = configs.load("h2o-danube-1.8b")["dims"]


def test_layer_params_by_hand():
    # yi: q 4096x4096, k and v 4096x512, o 4096x4096, gate/up/down 4096x11008
    assert work.layer_matmul_params(YI) == (
        2 * 4096 * 4096 + 2 * 4096 * 512 + 3 * 4096 * 11008)
    # danube: head_dim 2560/32 = 80, kv 8x80 = 640
    assert DANUBE["head_dim"] == 80
    assert work.layer_matmul_params(DANUBE) == (
        2 * 2560 * 2560 + 2 * 2560 * 640 + 3 * 2560 * 6912)


def test_weight_bytes_by_hand():
    layers = YI["layers"]
    per_layer = 2 * 4096 * 4096 + 2 * 4096 * 512 + 3 * 4096 * 11008 + 2 * 4096
    assert work.weight_bytes(YI) == 2 * (layers * per_layer + 4096
                                         + 4096 * 64000)
    assert work.weight_bytes(DANUBE) == 2 * (
        24 * (2 * 2560 * 2560 + 2 * 2560 * 640 + 3 * 2560 * 6912 + 2 * 2560)
        + 2560 + 2560 * 32000)


def test_decode_bytes_count_attended_positions_not_the_cache():
    # a step at position 99 attends 100 positions: K and V, kv_heads x hd
    kv = YI["layers"] * 2 * 4 * 128 * 2 * 100
    assert work.decode_step_bytes(YI, 99) == work.weight_bytes(YI) + kv
    assert work.decode_bytes(YI, 99, 3) == sum(
        work.decode_step_bytes(YI, p) for p in (99, 100, 101))
    # the window caps what a step attends
    assert work.decode_step_bytes(DANUBE, 10_000) == \
        work.decode_step_bytes(DANUBE, 4095)


def test_prefill_flops_by_hand():
    d = dict(YI, layers=1, window=None)
    # 3 new tokens after 2 cached: queries attend 3, 4, 5 keys
    dense = 2 * 3 * work.layer_matmul_params(d)
    attn = 4 * 32 * 128 * (3 + 4 + 5)
    head = 2 * 4096 * 64000
    assert work.prefill_flops(d, 3, 2) == dense + attn + head
    w = dict(d, window=4)
    assert work.prefill_flops(w, 3, 2) == dense + 4 * 32 * 128 * (3 + 4 + 4) \
        + head


def test_payload_and_charge():
    # the store charges per cached token; a payload holds the whole ring
    assert work.charged_bytes_per_token(YI) == YI["layers"] * 2 * 4 * 128 * 2
    assert work.payload_bytes(YI, 4096) == \
        work.charged_bytes_per_token(YI) * 4096
    assert work.payload_bytes(DANUBE, 8192) == \
        work.charged_bytes_per_token(DANUBE) * 4096
