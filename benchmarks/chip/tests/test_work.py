import pytest

import work


def test_256_cubed_step_count():
    cells = 256 ** 3
    assert work.step_bytes(cells) == 738_197_504          # 11 fields x 4 B
    assert work.step_flops(cells, 60) == 11_844_714_496   # 706 a cell
    least, bound = work.least_step_seconds(cells, 60, "TPU v5 lite")
    assert bound == "bytes"
    assert least == pytest.approx(738_197_504 / 819e9)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        work.peaks("cpu")
    assert not any("cpu" in k.lower() for k in work.PEAKS)
