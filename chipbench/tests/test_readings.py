"""The readings check, quantiles, arrivals, roofline functions."""

import statistics

import numpy as np
import pytest

from chipbench import readings as rd
from chipbench import roofline
from chipbench.arrivals import arrival_times


@pytest.mark.parametrize("n", [0, 1, 11])
def test_fewer_than_twelve_readings_are_refused(n):
    with pytest.raises(rd.TooFewReadings):
        rd.require_readings([0.1] * n)
    rd.require_readings([0.1] * 12)


def test_quantile_interpolates_like_the_statistics_module():
    xs = [3.0, 1.0, 4.0, 1.5, 9.0, 2.6, 5.0]
    assert rd.quantile(xs, 0.5) == statistics.median(xs)
    assert rd.quantile(xs, 0.0) == 1.0 and rd.quantile(xs, 1.0) == 9.0
    assert rd.quantile(xs, 0.9) == pytest.approx(np.quantile(xs, 0.9))
    d = rd.describe(xs)
    assert d["count"] == 7 and d["q1"] <= d["median"] <= d["q3"]


def test_arrivals_have_a_fixed_count_at_an_even_rate():
    a = arrival_times(1000, 20.0, np.random.default_rng(3))
    assert len(a) == 1000 and list(a) == sorted(a)
    assert 0.0 <= a[0] and a[-1] < 20.0
    assert 400 < (a < 10.0).sum() < 600
    assert list(arrival_times(50, 9.0, np.random.default_rng(1))) == \
        list(arrival_times(50, 9.0, np.random.default_rng(1)))


def test_flop_and_byte_functions_against_known_sizes():
    # gpt2-xl: 48 x 12 x 1600^2 + 1600 x 50257 multiplied weights
    assert roofline.gpt_matmul_params(48, 1600, 50257) == 1_554_971_200
    flops, nbytes = roofline.gpt_decode_step_needs(
        n_layer=48, n_embd=1600, vocab=50257, rows=1, live_positions=0,
        weight_bytes=2, kv_bytes=2)
    assert flops == 2 * 1_554_971_200
    assert nbytes == 2 * 1_554_971_200 + 4 * 50257
    peaks = roofline.peaks_for("TPU v5 lite")
    t, bound = roofline.least_time_s(flops, nbytes, peaks)
    assert bound == "memory" and t == pytest.approx(nbytes / 819e9)
    with pytest.raises(KeyError):
        roofline.peaks_for("TPU v9 imaginary")
