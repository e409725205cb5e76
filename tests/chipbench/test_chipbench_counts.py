"""The byte count of ``walk_roofline.walk`` and the operation count of
``mfu.fleet``, against shapes worked by hand."""
import pytest

from chipbench import counts


@pytest.mark.parametrize(
    "deg, jump, dist, words",
    [
        # MH move at degree 2: 3 words of I/O, 2 row pointers, the total,
        # ceil(log2 2) = 1 probe, the neighbour id
        (2, False, 0, 3 + 2 + 1 + 1 + 1),
        # MH move at a degree-1196 hub: ceil(log2 1196) = 11 probes
        (1196, False, 0, 3 + 2 + 1 + 11 + 1),
        # degree 5 (a grid node): 3 probes
        (5, False, 0, 3 + 2 + 1 + 3 + 1),
        # a jump of 3 hops: 3 words a hop, whatever the degree
        (1196, True, 3, 3 + 9),
        (2, True, 1, 3 + 3),
    ],
)
def test_walk_bytes_by_hand(deg, jump, dist, words):
    assert counts.walk_bytes([deg], [jump], [dist]) == 4 * words


def test_walk_bytes_sum_over_walker_steps():
    deg = [[2, 5], [1196, 2]]
    jump = [[False, True], [False, False]]
    dist = [[1, 2], [3, 1]]
    assert counts.walk_bytes(deg, jump, dist) == 4 * (8 + 9 + 18 + 8)


def test_walk_flops_by_hand():
    assert counts.walk_flops([False, True, True], [1, 1, 3]) == 1 + 5 + 7


def test_fleet_step_flops_small_shape():
    # n = 4 rows, dim = 2, W = 3 walkers, averaging every 2 steps
    n, dim, w, avg = 4, 2, 3, 2
    sgd = 4 * dim + 4  # 12 per walker
    loss = 2 * n * dim + 3 * n + 1  # 29 per walker, and for the averaged model
    mean = dim * w + dim  # 8
    expected = w * (sgd + loss) + (mean + loss) + mean / avg
    assert counts.fleet_step_flops(n, dim, w, avg) == expected == 164.0


def test_fleet_step_flops_cell_shape_is_the_loss_product():
    # at the cell's shape the (n, dim) @ (dim, W) product is 87% of the step
    total = counts.fleet_step_flops(100_000, 10, 8192, 4)
    assert 2 * 100_000 * 10 * 8192 / total == pytest.approx(0.8696, abs=1e-3)
    assert counts.fleet_step_flops(100_000, 10, 8192, 0) < total
