"""Tests for the TDC models and the three-domain comparison engine
(paper §III-A, §IV, Figs. 7/9/11/12)."""
import math

import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import analog, chain, design_space as ds, digital, tdc
from repro.core import constants as C


class TestTDC:
    def test_optimal_losc_matches_numeric_argmin(self):
        """Eq. 9 closed form + refinement lands on the Eq. 8 minimum."""
        for units in (100, 1000, 10000, 100000):
            l_opt = tdc.optimal_l_osc(units)
            e_opt = tdc.hybrid_tdc_energy(units, l_opt)
            grid = range(max(1, l_opt // 4), l_opt * 4 + 2)
            e_best = min(tdc.hybrid_tdc_energy(units, l) for l in grid)
            assert e_opt <= e_best * 1.0 + 1e-22

    @pytest.mark.parametrize("log2_ulps", [0, 1, -1])
    def test_bit_counts_exact_at_powers_of_two(self, monkeypatch, log2_ulps):
        """The array paths' ceil(log2) bit counts (TDC range and LSB bits,
        adder-tree depth) equal the float64 scalar path's at, just below
        and just above powers of two, even when float32 log2 is off by an
        ulp, as a TPU's is (log2(8192) = 13.000001 on a v5e)."""
        if log2_ulps:
            log2 = jnp.log2
            toward = np.float32(np.inf * log2_ulps)
            monkeypatch.setattr(jnp, "log2",
                                lambda x: jnp.nextafter(log2(x), toward))
        xs = []
        for k in range(1, 20):
            p = np.float32(2.0 ** k)
            xs += [np.nextafter(p, np.float32(0)), p,
                   np.nextafter(p, np.float32(np.inf))]
        xs = np.asarray(xs, np.float32)
        want = [math.ceil(math.log2(float(x))) for x in xs]
        assert np.asarray(tdc.ceil_log2(xs)).tolist() == want
        assert np.asarray(tdc.range_bits(xs)).tolist() == \
            [tdc.range_bits(float(x)) for x in xs]
        assert np.asarray(tdc._lsb_bits(xs)).tolist() == \
            [tdc._lsb_bits(float(x)) for x in xs]
        ints = np.asarray([2 ** k for k in range(1, 20)], np.float32)
        np.testing.assert_array_equal(
            digital._adder_bits_per_mac(ints, 4),
            np.float32([digital._adder_bits_per_mac(int(n), 4)
                        for n in ints]))

    def test_sar_energy_formula(self):
        """Eq. 10 literal check."""
        b, m = 6, 8
        want = C.E_TD_AND * (m + 1) / m * (2 ** b - 2) + b * C.E_SAMPLE
        got = tdc.sar_tdc_energy(b, m)
        assert np.isclose(got, want, rtol=1e-6)

    def test_fig7_sar_wins_binary_hybrid_wins_multibit(self):
        """Fig. 7: SAR better at B=1 (counter overhead), hybrid at B>=2."""
        e_h1 = tdc.tdc_energy_per_vmm(576, 1, 1, m=8, arch="hybrid")
        e_s1 = tdc.tdc_energy_per_vmm(576, 1, 1, m=8, arch="sar")
        assert e_s1 < e_h1
        for b in (2, 4, 8):
            e_h = tdc.tdc_energy_per_vmm(576, b, 1, m=8, arch="hybrid")
            e_s = tdc.tdc_energy_per_vmm(576, b, 1, m=8, arch="sar")
            assert e_h < e_s, b

    @given(st.integers(50, 50000))
    @settings(max_examples=20, deadline=None)
    def test_hybrid_energy_monotone_in_range(self, units):
        l = tdc.optimal_l_osc(units)
        e1 = tdc.hybrid_tdc_energy(units, l)
        e2 = tdc.hybrid_tdc_energy(units * 2, tdc.optimal_l_osc(units * 2))
        assert e2 > e1

    def test_range_clipping(self):
        """Fig. 6: effective range ~ kappa sqrt(N) (2^B - 1) < full."""
        full = tdc.effective_range_steps(576, 4, clip_to_observed=False)
        eff = tdc.effective_range_steps(576, 4, clip_to_observed=True)
        assert eff < full
        assert np.isclose(eff, C.RANGE_KAPPA * math.sqrt(576) * 15)


class TestAnalog:
    def test_adc_energy_eq12(self):
        assert np.isclose(analog.adc_energy(8.0),
                          C.K1_ADC * 8 + C.K2_ADC * 4 ** 8)

    def test_enob_eq13(self):
        """ENOB = (SNR_dB - 1.76)/6.02."""
        enob = analog.enob_for_sigma(1024.0, 1.0)
        snr_db = 20 * math.log10(1024.0)
        assert np.isclose(enob, (snr_db - 1.76) / 6.02, rtol=1e-6)

    def test_relaxing_sigma_lowers_enob_and_energy(self):
        tight = analog.analog_energy_per_mac(576, 4, sigma_max=0.17)
        loose = analog.analog_energy_per_mac(576, 4, sigma_max=2.0)
        assert loose["enob"] < tight["enob"]
        assert loose["e_mac"] < tight["e_mac"]


class TestDomainComparison:
    def test_fig9_exact_digital_dominates_multibit(self):
        s = ds.sigma_exact()
        for n in (64, 576, 2048):
            for b in (2, 4, 8):
                pts = {d: ds.evaluate(d, n, b, s).e_mac for d in ds.DOMAINS}
                assert min(pts, key=pts.get) == "digital", (n, b, pts)

    def test_fig11_relaxed_td_wins_small_analog_wins_large(self):
        """Fig. 11 crossovers at B=4, sigma = 2 LSB."""
        win = {n: min(ds.DOMAINS,
                      key=lambda d: ds.evaluate(d, n, 4, 2.0).e_mac)
               for n in (128, 256, 576, 2048, 4096)}
        assert win[256] == "td"
        assert win[576] == "td"
        assert win[2048] == "analog"
        assert win[4096] == "analog"

    def test_relaxed_beats_exact_for_td_and_analog(self):
        s_exact = ds.sigma_exact()
        for dom in ("td", "analog"):
            e_exact = ds.evaluate(dom, 576, 4, s_exact).e_mac
            e_relax = ds.evaluate(dom, 576, 4, 2.0).e_mac
            assert e_relax < e_exact
        # digital is accuracy-independent
        assert np.isclose(ds.evaluate("digital", 576, 4, s_exact).e_mac,
                          ds.evaluate("digital", 576, 4, 2.0).e_mac)

    def test_fig12_throughput_digital_dominates_large(self):
        for n in (576, 4096):
            pts = {d: ds.evaluate(d, n, 4, 2.0).throughput
                   for d in ds.DOMAINS}
            assert max(pts, key=pts.get) == "digital"

    def test_fig12_area_td_not_competitive_large_b(self):
        """'In terms of area requirements, TD generally is not competitive.'"""
        for n in (576, 4096):
            pts = {d: ds.evaluate(d, n, 8, 2.0).area_per_mac
                   for d in ds.DOMAINS}
            assert pts["td"] == max(pts.values()), (n, pts)

    @given(st.integers(16, 4096), st.sampled_from([1, 2, 4]))
    @settings(max_examples=15, deadline=None)
    def test_td_energy_decomposition(self, n, b):
        """Eq. 7: e_mac = e_cell + e_tdc / n."""
        p = ds.evaluate("td", n, b, 2.0)
        assert np.isclose(p.e_mac, p.aux["e_cell"] + p.aux["e_tdc"] / n,
                          rtol=1e-6)

    def test_vdd_optimized_td_no_worse(self):
        base = ds.evaluate("td", 576, 4, 2.0).e_mac
        opt = ds.td_vdd_optimized(576, 4, 2.0).e_mac
        assert opt <= base * (1 + 1e-9)


class TestDigital:
    def test_energy_grows_with_bits_and_depth(self):
        assert digital.digital_energy_per_mac(576, 8) > \
            digital.digital_energy_per_mac(576, 2)
        assert digital.digital_energy_per_mac(4096, 4) > \
            digital.digital_energy_per_mac(64, 4)

    def test_throughput_single_cycle(self):
        assert digital.digital_throughput(576, 4, m=8) == 576 * 8 * C.F_DIG
