"""The yardstick's arithmetic: Table II "True Out" and the kernel's bytes."""
import pytest

from bench import cost

# published statistics: nodes, undirected edges, feature nonzeros, widths
TABLE_II = {
    "cora": ((2708, 5278, 49216, (1433, 16, 7)), 2.79e6),
    "citeseer": ((3327, 4552, 105165, (3703, 16, 6)), 4.56e6),
    "pubmed": ((19717, 44324, 985850, (500, 16, 3)), 37.52e6),
    "nell": ((65755, 133072, 92057, (5414, 64, 186)), 1743e6),
}


@pytest.mark.parametrize("name", sorted(TABLE_II))
def test_true_ops_reproduce_table_ii(name):
    (nodes, edges, nnz, dims), paper = TABLE_II[name]
    ops = cost.true_ops(nodes, edges, nnz, dims)
    assert abs(ops - paper) <= 0.005 * paper


def test_true_ops_pubmed_exact():
    assert cost.true_ops(19717, 44324, 985850, (500, 16, 3)) == 37_557_902


def test_spmm_abft_cost_pubmed_staged_shape():
    # PubMed at block 128: 155 stripes x ELL width 155, f32 tiles, i32 cols
    ops, nbytes = cost.spmm_abft_cost((155, 155, 128, 128), 4, (155, 155), 4,
                                      16)
    tiles = 155 * 155
    assert tiles * 128 * 128 * 4 == 1_574_502_400      # the stored tiles
    assert ops == 2 * tiles * 128 * 128 * 17
    assert nbytes == (1_574_502_400 + tiles * 4 + tiles * 128 * 17 * 4
                      + 155 * 128 * 17 * 4 + 155 * 4)
    least, bound = cost.least_seconds(
        ops, nbytes, {"flops_per_s": 197e12, "bytes_per_s": 819e9})
    assert bound == "memory"
    assert least == pytest.approx(nbytes / 819e9)
