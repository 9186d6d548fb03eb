"""Scoreboard invariants (paper Sec. 3, Fig. 5) — property-based."""
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import hasse
from repro.core.patterns import tile_stats
from repro.core.scoreboard import (dynamic_scoreboard, static_scoreboard,
                                   static_tile_stats)


def _rows(seed, tiles=4, n=64, t=8):
    return np.random.default_rng(seed).integers(
        0, 1 << t, size=(tiles, n)).astype(np.uint32)


@given(seed=st.integers(0, 2**31 - 1), t=st.sampled_from([4, 8]))
@settings(max_examples=25, deadline=None)
def test_prefix_is_subset_distance1(seed, t):
    """Every executed non-outlier node's selected prefix is a covering
    (one-bit-cleared) subset — the forest edges are Hasse edges."""
    rows = _rows(seed, t=t, n=48)
    si = dynamic_scoreboard(rows, t)
    exe = si.executed
    for ti in range(si.tiles):
        for node in np.nonzero(exe[ti])[0]:
            pre = si.prefix[ti, node]
            assert pre >= 0, (ti, node)
            assert hasse.is_prefix(pre, node)
            assert hasse.popcount(np.uint64(node ^ pre)) == 1

    # lanes: every executed node carries the lane of its prefix
    for ti in range(si.tiles):
        for node in np.nonzero(exe[ti])[0]:
            pre = si.prefix[ti, node]
            if pre > 0:
                assert si.lane[ti, node] == si.lane[ti, pre]
            else:
                assert si.lane[ti, node] == int(np.log2(node))


@given(seed=st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_all_present_nodes_executable(seed):
    """Every present TransRow value is either executed or an outlier."""
    rows = _rows(seed)
    si = dynamic_scoreboard(rows, 8)
    covered = si.executed | si.outlier
    for ti in range(si.tiles):
        present = np.unique(rows[ti])
        present = present[present != 0]
        assert covered[ti, present].all()


def test_paper_fig1_example():
    """Fig. 1: rows {1011,1111,0011,0010} need 4 ops vs 10 bit-sparse."""
    si = dynamic_scoreboard(
        np.array([[0b1011, 0b1111, 0b0011, 0b0010]]), 4)
    st_ = tile_stats(si)
    assert st_.ppe_ops[0] == 4
    assert st_.bit_ops[0] == 10
    assert st_.tr[0] == 0


def test_density_bounds_random_t8():
    """Sec. 5.2: runtime density ~1/T at N=256; PPE density below it;
    bit density ~0.5; distances: none >= 4 at N=256."""
    rows = _rows(1, tiles=32, n=256)
    st_ = tile_stats(dynamic_scoreboard(rows, 8))
    d = st_.density.mean()
    assert 0.118 < d < 0.135, d
    assert (st_.density_ppe < st_.density + 1e-9).all()
    assert abs(st_.bit_density.mean() - 0.5) < 0.02
    assert st_.dist_hist[:, 4].sum() == 0


def test_expected_unique_nodes():
    """Sec. 5.9: E[#unique] of 256 uniform 8-bit TransRows ~= 162."""
    rows = _rows(2, tiles=64, n=256)
    si = dynamic_scoreboard(rows, 8)
    mean_unique = si.present.sum(-1).mean()
    assert abs(mean_unique - 162) < 3, mean_unique


def test_zero_rows_skipped():
    si = dynamic_scoreboard(np.zeros((1, 16), np.uint32), 8)
    st_ = tile_stats(si)
    assert st_.ppe_ops[0] == 0 and st_.ape_ops[0] == 0
    assert st_.zr[0] == 16


def test_static_vs_dynamic_density_crossover():
    """Fig. 13: static SI matches dynamic at large tile rows, degrades at
    small tile rows (SI misses)."""
    rng = np.random.default_rng(3)
    all_rows = rng.integers(0, 256, size=(1 << 14,)).astype(np.uint32)
    ssi = static_scoreboard(all_rows, 8)

    def density(tile_rows):
        tiles = all_rows.reshape(-1, tile_rows)[:16]
        s = static_tile_stats(ssi, tiles)
        return (np.maximum(s["ppe"], s["ape"]) / s["dense"]).mean()

    d64, d1024 = density(64), density(1024)
    dyn64 = tile_stats(dynamic_scoreboard(
        all_rows.reshape(-1, 64)[:16], 8)).density.mean()
    assert d64 > dyn64          # SI misses hurt small tiles
    assert d1024 < d64 * 0.75   # and wash out at large tiles


def test_load_balance():
    """Balanced forest: max-lane PPE load within 3x of mean (T=8, N=256)."""
    rows = _rows(4, tiles=16, n=256)
    si = dynamic_scoreboard(rows, 8)
    tot = si.wl_ppe.sum(-1)
    mx = si.wl_ppe.max(-1)
    assert (mx <= np.ceil(tot / 8 * 3)).all(), (mx, tot / 8)
