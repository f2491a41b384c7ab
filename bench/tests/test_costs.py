"""The yardstick's counts worked out by hand, at the shapes of the paper's
EGRU widened to n=256 (batch 4), where every term is large enough to tell
apart."""
from bench import costs

B, N, PC = 4, 256, 21504     # batch, hidden units, live columns (padded)


def test_ragged_influence_flops_by_hand():
    # 2 * Pc * sum_b K_b K'_b = 2 * 21504 * 4 * 100 * 100
    assert costs.ragged_influence_update_flops([100] * B, [100] * B, PC) \
        == 1_720_320_000.0
    # ragged rows: 2 * 21504 * (100*110 + 90*80 + 50*60 + 0*7)
    assert costs.ragged_influence_update_flops(
        [100, 90, 50, 0], [110, 80, 60, 7], PC) == 2 * 21504 * 21200


def test_influence_bytes_by_hand():
    # carry read + write 2 * 4*100*21504 * 4 B = 68_812_800
    # J-hat 4*256*256*4 = 1_048_576; M-bar rows 4*100*21504*4 = 34_406_400
    # side arrays 2*4*100*4 + 4*100*4 + 2*4*4 = 4_832
    assert costs.influence_update_bytes(B, 100, 100, PC, N) == 104_272_608
    # a bf16 carry halves only the carry term
    assert costs.influence_update_bytes(B, 100, 100, PC, N, dtype_bytes=2) \
        == 104_272_608 - 34_406_400


def test_step_flops_by_hand():
    nnz = 20_275                      # kept input and recurrent weights
    fwd = 2 * B * (nnz + N * 2)       # 166_296
    assert costs.forward_flops(B, nnz, N, 2) == fwd
    assert costs.grad_readout_flops([100] * B, PC) == 2 * PC * 400
    assert costs.step_flops(B, N, 2, nnz, PC, 100) \
        == fwd + 1_720_320_000 + 2 * PC * 400


def test_least_time_names_the_binding_bound():
    t, bound = costs.least_time_s(1_720_320_000, 104_272_608, 197e12, 819e9)
    assert bound == "memory"
    assert t == 104_272_608 / 819e9
    assert costs.least_time_s(1e12, 1.0, 1e12, 1e9) == (1.0, "compute")
