"""The roofline's work function counts real widths, and the peaks table
refuses a chip it does not know."""
import pytest

from bench import roofline


def test_work_at_real_widths_is_below_the_padded_count():
    real = roofline.design_work(t=28, s_pe=1, s_mem=1, n_noc=1, n_wl=3)
    padded = roofline.design_work(t=128, s_pe=32, s_mem=32, n_noc=1, n_wl=3)
    assert real["bytes"] < padded["bytes"] and real["ops"] < padded["ops"]
    # ar_complex on the base design, term by term (see design_work)
    assert real["bytes"] == 4 * ((3 * 28 + 5 + 6 + 4 + 3 + 3) + (2 * 28 + 10 + 3))
    assert real["ops"] == 28 * 28 * 28


def test_work_grows_with_every_real_width():
    base = roofline.design_work(15, 5, 3, 2, 1)
    for kw in ({"t": 16}, {"s_pe": 6}, {"s_mem": 4}, {"n_noc": 3}):
        args = dict(t=15, s_pe=5, s_mem=3, n_noc=2, n_wl=1)
        args.update(kw)
        assert roofline.design_work(**args)["bytes"] > base["bytes"]


def test_share_names_its_bound_and_refuses_an_unknown_chip():
    share, bound = roofline.roofline_share(819e9, 1.0, 2.0, "TPU v5 lite")
    assert bound == "bytes" and share == pytest.approx(50.0)
    share, bound = roofline.roofline_share(1.0, 197e12, 4.0, "TPU v5 lite")
    assert bound == "ops" and share == pytest.approx(25.0)
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
