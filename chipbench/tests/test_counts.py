"""The FLOP and task-count functions against closed forms."""

import pytest

from chipbench.graphs import gemm, potrf


@pytest.mark.parametrize("n, ts, tasks", [
    (16384, 512, 5984),         # NT = 32
    (49152, 2048, 2600),        # NT = 24
    (96, 8, 364),               # NT = 12
])
def test_potrf_tasks(n, ts, tasks):
    nt = n // ts
    by_class = nt + nt * (nt - 1) + nt * (nt - 1) * (nt - 2) // 6
    assert potrf.tasks({"n": n, "ts": ts}) == tasks == by_class


def test_potrf_flops():
    assert potrf.flops({"n": 49152, "ts": 2048}) == \
        pytest.approx(3.958e13, rel=1e-3)
    assert potrf.flops({"n": 6, "ts": 2}) == 6 ** 3 / 3 + 6 ** 2 / 2


def test_potrf_dot_flops_are_the_trailing_updates():
    t = {"n": 16384, "ts": 512}
    dots = potrf.dot_flops(t)
    nt = 32
    assert dots["jit_tile_gemm_update"] == 4960 * 2 * 512 ** 3
    assert dots["jit_tile_syrk"] == nt * (nt - 1) // 2 * 2 * 512 ** 3
    assert set(dots) <= set(potrf.KERNEL_MODULES)
    # the tile algorithm does ~N^3/3 in its dots, syrk computing full tiles
    assert sum(dots.values()) == pytest.approx(potrf.flops(t), rel=0.06)


@pytest.mark.parametrize("n, ts, tasks", [(28672, 2048, 2744),
                                          (8192, 512, 4096)])
def test_gemm_counts(n, ts, tasks):
    t = {"n": n, "ts": ts}
    assert gemm.tasks(t) == tasks
    assert gemm.flops(t) == 2.0 * n ** 3
    assert gemm.dot_flops(t) == {"jit_tile_gemm": tasks * 2.0 * ts ** 3}
