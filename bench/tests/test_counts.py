"""Kernel and step counts against hand-computed numbers."""
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
GRANITE = dict(n_heads=32, n_kv_heads=8, head_dim=64, page_size=128,
               pages_per_slot=32)
CHATGLM = dict(n_heads=32, n_kv_heads=2, head_dim=128, page_size=128,
               pages_per_slot=32)


@pytest.mark.parametrize("shape, kv_head_bytes", [
    (GRANITE, 8 * 64 * 128), (CHATGLM, 2 * 128 * 128)])
def test_decode_row(shape, kv_head_bytes):
    # one decode row at position 299: 300 visible positions, 3 pages
    ops, nbytes = PA.call([(299, 1)], **shape)
    hd, H = shape["head_dim"], shape["n_heads"]
    assert ops == 2 * 2 * hd * 300 * H
    pages = 3
    assert nbytes == (2 * pages * kv_head_bytes  # K and V, once per kv head
                      + H * hd * 1 + H * hd * 4  # int8 q, int32 out
                      + 4 * 32)                  # page table


def test_chunk_row_is_causal():
    # 128 queries at 256..383 see 257..384 positions
    ops, _ = PA.call([(256, 128)], **GRANITE)
    visible = sum(256 + s + 1 for s in range(128))
    assert ops == 4 * 64 * 32 * visible


def test_bytes_do_not_grow_with_group():
    """Folding more query heads onto the same kv heads adds no K/V
    bytes: only q and the output grow."""
    rows = [(1000, 1), (10, 128)]
    base = dict(GRANITE, n_heads=8)     # group 1
    wide = dict(GRANITE, n_heads=32)    # group 4
    _, b1 = PA.call(rows, **base)
    _, b4 = PA.call(rows, **wide)
    qo = sum(n for _, n in rows) * 64 * 5
    assert b4 - b1 == (32 - 8) * qo


def test_kv4_halves_page_bytes():
    _, b8 = PA.call([(4095, 1)], **GRANITE, kv_bits=8)
    _, b4 = PA.call([(4095, 1)], **GRANITE, kv_bits=4)
    assert b8 - b4 == 32 * 8 * 64 * 128  # 32 pages, K and V at half


def test_roofline_picks_the_larger_bound():
    peak = {"int8_ops_s": 393e12, "hbm_bytes_s": 819e9}
    assert PA.roofline_s(393e12, 1, peak) == pytest.approx(1.0)
    assert PA.roofline_s(1, 819e9, peak) == pytest.approx(1.0)


def test_step_ops_granite_decode():
    from harness import spec

    ms = spec.reference("dense_swiglu")
    d = ms.Dims(n_layers=40, d_model=2048, n_heads=32, n_kv_heads=8,
                head_dim=64, d_ff=8192, vocab=49155)
    per_layer = 2048 * (32 + 16) * 64 + 32 * 64 * 2048 + 3 * 2048 * 8192
    assert ms.layer_weights(d) == per_layer
    ops = ms.step_ops([(99, 1)], d)
    assert ops == (2 * per_layer * 40 + 2 * 2048 * 49155
                   + 40 * 4 * 64 * 32 * 100)
