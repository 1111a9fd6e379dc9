"""``called_in_push_share``: the reader divides the ``ptdev`` lane's two
always-on counts, and gives nothing where no program ran or where the
program keeps no such count (a program that pushes a whole round before
its first call)."""
import pytest

from chipbench.layers import called_in_push_share


def test_called_in_push_share_divides_the_lanes_two_counters(monkeypatch):
    from parsec_tpu.device.native import PTDEV_STATS

    monkeypatch.setitem(PTDEV_STATS, "programs", 10 * 47)
    monkeypatch.setitem(PTDEV_STATS, "called_in_push", 10 * 19)
    assert called_in_push_share.read(None) == pytest.approx(40.4255, abs=1e-4)
    monkeypatch.setitem(PTDEV_STATS, "called_in_push", 0)
    assert called_in_push_share.read(None) == 0.0
    # no program ran, or a program without the count: nothing to read
    monkeypatch.setitem(PTDEV_STATS, "programs", 0)
    assert called_in_push_share.read(None) is None
    monkeypatch.setitem(PTDEV_STATS, "programs", 47)
    monkeypatch.delitem(PTDEV_STATS, "called_in_push")
    assert called_in_push_share.read(None) is None
