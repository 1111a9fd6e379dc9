"""``tiles_per_put`` (PR 38): the reader divides the ``ptdev`` lane's two
always-on counts, and gives nothing where no tile moved or where the program
keeps no such count (the parent of the PR that added it)."""
import pytest

from chipbench.layers import tiles_per_put


def test_tiles_per_put_divides_the_lanes_two_counters(monkeypatch):
    from parsec_tpu.device.native import PTDEV_STATS

    monkeypatch.setitem(PTDEV_STATS, "staged_tiles", 10 * 528)
    monkeypatch.setitem(PTDEV_STATS, "stage_in_puts", 10 * 6)
    assert tiles_per_put.read(None) == pytest.approx(88.0)
    # no tile moved (a pool of hits and adoptions), or a program that puts
    # a tile a call and keeps no such count: nothing to read
    monkeypatch.setitem(PTDEV_STATS, "staged_tiles", 0)
    monkeypatch.setitem(PTDEV_STATS, "stage_in_puts", 0)
    assert tiles_per_put.read(None) is None
    monkeypatch.delitem(PTDEV_STATS, "stage_in_puts")
    assert tiles_per_put.read(None) is None
    monkeypatch.setitem(PTDEV_STATS, "stage_in_puts", 6)
    monkeypatch.delitem(PTDEV_STATS, "staged_tiles")
    assert tiles_per_put.read(None) is None
