"""FLOP and byte counts at known shapes, and the bound that keeps
``step_mfu`` and ``partial_prefill_roofline`` at or under 100%."""
import numpy as np
import pytest

from chipbench import cells, flops, harness


class _Cfg:
    n_layers, n_heads, n_kv_heads, head_dim = 2, 4, 2, 16
    d_model, d_ff, vocab = 64, 128, 256


def test_matmul_flops_per_row_at_a_known_shape():
    per_layer = 64 * 64 + 2 * 64 * 32 + 64 * 64 + 3 * 64 * 128
    assert flops.matmul_flops_per_row(_Cfg) == 2 * (2 * per_layer + 64 * 256)


def test_step_and_kernel_counts_at_a_known_shape():
    pos = np.array([[10, 11, -1, -1], [-1, -1, -1, -1]])
    attn = 4 * 4 * 16 * 2 * (11 + 12)
    assert flops.step_flops(_Cfg, pos) == \
        2 * flops.matmul_flops_per_row(_Cfg) + attn
    f, b = flops.kernel_cost(_Cfg, pos)
    assert f == attn
    # 12 context positions of K and V (2 KV heads x 16, bf16) and their
    # int32 positions, plus 2 rows of bf16 queries and outputs, per layer
    assert b == 2 * (12 * (2 * 16 * 2 * 2 + 4) + 2 * 4 * 16 * 2 * 2)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_counts_never_exceed_the_padded_work(seed):
    """What the step computes for a padded (B, C) feed over each slot's
    mapped context bounds the logical counts from above, so a measured
    share of peak or roofline cannot pass 100%."""
    rng = np.random.default_rng(seed)
    B, C = 4, 32
    pos = np.full((B, C), -1)
    ctx = np.zeros(B, int)
    for b in range(B):
        n = rng.integers(0, C + 1)
        start = rng.integers(0, 200)
        pos[b, :n] = start + np.arange(n)
        ctx[b] = start + n
    padded_step = flops.matmul_flops_per_row(_Cfg) * B * C + \
        flops.attention_flops(_Cfg, np.repeat(ctx + C, C))
    assert flops.step_flops(_Cfg, pos) <= padded_step
    f, b = flops.kernel_cost(_Cfg, pos)
    L, nh, nkv, hd = 2, 4, 2, 16
    assert f <= L * 4 * nh * hd * C * (ctx + C).sum()
    assert b <= L * ((ctx + C).sum() * (nkv * hd * 2 * 2 + 4) * nh
                     + B * C * nh * hd * 4)


def test_least_seconds_takes_the_binding_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.least_seconds(1000, 50, peak) == pytest.approx(10.0)
    assert flops.least_seconds(100, 50, peak) == pytest.approx(5.0)


def test_peaks_are_kept_by_device_kind_with_their_source():
    table = cells.load_json(cells.HERE / "peaks.json")
    assert "TPU v5e" in table["source"]
    p = harness.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v99 imaginary")
    with pytest.raises(KeyError):
        harness.peaks_for("cpu")
