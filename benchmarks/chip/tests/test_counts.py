"""Hand-counted operations and bytes of the count functions, and the
model FLOPs per token of granite-3-2b's reference module."""
import pytest

import cell
import counts


def _granite():
    """granite-3-2b's reference module and its full published widths."""
    c = cell.load("granite-3-2b.decode")
    return c.reference, c.reference.Dims.from_config(c.config)


def test_kv_token_bytes():
    # hd 64: bf16 K+V = 2 * 64 * 2; int4 K+V = 2 * (32 packed + 2 scale)
    assert counts.kv_token_bytes(64, "normal") == 256
    assert counts.kv_token_bytes(64, "int4") == 68
    assert counts.kv_token_bytes(64, "int8") == 132


def test_paged_attention_granite_one_row():
    # one row, 100 int4 tokens, granite heads: 32 query heads, 8 KV heads
    flops, nbytes = counts.paged_attention([(0, 100)], n_heads=32,
                                           n_kv_heads=8, head_dim=64)
    assert flops == 4 * 32 * 64 * 100                   # q.K and p.V
    assert nbytes == 8 * 100 * 68 + 2 * 32 * 64 * 2     # KV + q and out


def test_paged_attention_mixed_modes_and_rows():
    rows = [(16, 0), (0, 32)]       # a Normal page; two Augmented pages
    flops, nbytes = counts.paged_attention(rows, n_heads=2, n_kv_heads=1,
                                           head_dim=8)
    assert flops == 4 * 2 * 8 * (16 + 32)
    kv = 16 * (2 * 8 * 2) + 32 * (2 * (4 + 2))
    assert nbytes == kv + 2 * (2 * 2 * 8 * 2)


def test_dual_plane_matmul():
    flops, nbytes = counts.dual_plane_matmul(16, 2048, 8192)
    assert flops == 2 * 16 * 2048 * 2 * 8192
    assert nbytes == 2048 * 8192 + 2 * 8192 * 4 + 16 * 2048 * 2 \
        + 2 * 16 * 8192 * 2


def test_decode_token_flops_granite():
    ref, dims = _granite()
    assert (dims.n_layers, dims.d_model, dims.n_heads, dims.n_kv_heads,
            dims.head_dim, dims.d_ff, dims.vocab) == \
        (40, 2048, 32, 8, 64, 8192, 49155)
    per_layer = 2048 * 2048 + 2 * 2048 * 512 + 2048 * 2048 + 3 * 2048 * 8192
    params = 40 * per_layer + 2048 * 49155
    assert ref.matmul_params(dims) == params
    assert ref.decode_token_flops(dims, 1000) == \
        2 * params + 4 * 40 * 32 * 64 * 1000


# the integers the count gave when it lived in counts.py
@pytest.mark.parametrize("context,flops", [
    (1, 5067059200), (800, 5328875520), (2048, 5737820160)])
def test_dense_decode_token_flops_unchanged(context, flops):
    ref, dims = _granite()
    assert ref.decode_token_flops(dims, context) == flops


def test_roofline_share_names_its_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    share, bound = counts.roofline_share(50.0, 20.0, 4.0, peaks)
    assert bound == "memory" and share == pytest.approx(50.0)
    share, bound = counts.roofline_share(800.0, 20.0, 10.0, peaks)
    assert bound == "compute" and share == pytest.approx(80.0)


def test_unknown_device_kind_is_an_error():
    assert counts.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        counts.peaks_for("TPU v9 imaginary")
