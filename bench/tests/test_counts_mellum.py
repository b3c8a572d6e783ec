"""The windowed paged-attention count and the grouped expert count
against hand-computed numbers (mellum2_12b_l4's shapes)."""
import importlib.util
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(name):
    path = os.path.join(os.path.dirname(HERE), "counts", f"{name}.py")
    spec = importlib.util.spec_from_file_location("c_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


PA = _load("paged_attention")
PAW = _load("paged_attention_windowed")
MOE = _load("moe_experts")
MELLUM = dict(n_heads=32, n_kv_heads=4, head_dim=128, page_size=128,
              pages_per_slot=32)
W = 1024
PAGE = 2 * 4 * 128 * 128  # K and V of 4 kv heads, one page, int8
QO = 32 * 128 * 5  # int8 q and int32 out of one query
TABLE = 4 * 32


@pytest.mark.parametrize("rows", [
    [(99, 1)], [(2999, 1)], [(1000, 128)], [(0, 128), (4095, 1), (7, 1)]])
def test_no_window_is_the_plain_count(rows):
    assert PAW.call(rows, **MELLUM) == PA.call(rows, **MELLUM)
    assert PAW.call(rows, **MELLUM, window=None) == PA.call(rows, **MELLUM)


def test_row_inside_the_window():
    # a decode row at 99 sees 100 positions, all on page 0
    ops, nbytes = PAW.call([(99, 1)], **MELLUM, window=W)
    assert ops == 4 * 128 * 32 * 100
    assert nbytes == PAGE + QO + TABLE


def test_row_past_the_window():
    # a decode row at 2999 sees 1976..2999: pages 15..23
    ops, nbytes = PAW.call([(2999, 1)], **MELLUM, window=W)
    assert ops == 4 * 128 * 32 * 1024
    assert nbytes == 9 * PAGE + QO + TABLE


def test_chunk_row_across_the_window_edge():
    # queries at 1000..1127: the first 24 see 1001..1024 positions, the
    # other 104 see 1024; query 0 sees from position 0, so pages 0..8
    ops, nbytes = PAW.call([(1000, 128)], **MELLUM, window=W)
    assert ops == 4 * 128 * 32 * ((1001 + 1024) * 24 // 2 + 104 * 1024)
    assert nbytes == 9 * PAGE + 128 * QO + TABLE


EXPERT = 3 * 2304 * 896  # gate, up and down of one expert, int8
MOE_SHAPE = dict(d_model=2304, d_expert=896, n_experts=64, top_k=8)


def test_moe_fewer_pairs_than_experts():
    # 4 tokens x 8 = 32 pairs reach at most 32 of the 64 experts
    ops, nbytes = MOE.call(4, **MOE_SHAPE)
    assert ops == 6 * 2304 * 896 * 32
    assert nbytes == 32 * EXPERT + 2 * 32 * 2304


def test_moe_every_expert_read_once():
    # a decode step of 32 rows: 256 pairs, every expert read once
    ops, nbytes = MOE.call(32, **MOE_SHAPE)
    assert ops == 6 * 2304 * 896 * 256
    assert nbytes == 64 * EXPERT + 2 * 256 * 2304


def test_step_ops_mellum_decode():
    from harness import spec

    ms = spec.reference("moe_swa")
    d = ms.Dims(n_layers=4, d_model=2304, n_heads=32, n_kv_heads=4,
                head_dim=128, d_expert=896, n_experts=64, top_k=8,
                vocab=98304, window=W)
    per_token = (2304 * 40 * 128 + 32 * 128 * 2304 + 2304 * 64
                 + 8 * 3 * 2304 * 896)
    assert ms.token_weights(d) == per_token
    attn = 4 * 128 * 32 * (3 * 1024 + 3000)  # three sliding, one full
    assert ms.step_ops([(2999, 1)], d) == (
        2 * per_token * 4 + 2 * 2304 * 98304 + attn)
    assert ms.windows(d) == [W, W, W, None]
