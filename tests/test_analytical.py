"""§5 analytical model: thresholds, step function, closed form, time cost."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import analytical as an

PROD = (1, 4, 7, 11)


def test_thetas_production():
    th = an.thetas(PROD, 5)
    # theta_0 = 2, then +15, +127, +2047, then repeat +2047
    assert th.tolist() == [2, 17, 144, 2191, 4238, 6285]


def test_step_function_paper_values():
    # f=1,2 fit in the first 2^1 slice: M = 2
    assert an.memory_slots(PROD, [1, 2]).tolist() == [2, 2]
    # f=3..17 need slice 2 (16 slots incl. ptr): M = 17 + 1
    assert an.memory_slots(PROD, [3, 17]).tolist() == [18, 18]
    # f=18..144: M = 144 + 2
    assert an.memory_slots(PROD, [18, 144]).tolist() == [146, 146]
    # f=145..2191: M = 2191+3
    assert an.memory_slots(PROD, [145, 2191]).tolist() == [2194, 2194]
    # beyond: repeat pool-4 slices
    assert an.memory_slots(PROD, [2192]).tolist() == [4238 + 4]


@st.composite
def z_strategy(draw):
    P = draw(st.sampled_from([2, 4, 6, 8]))
    return tuple(sorted(draw(st.lists(st.integers(0, 12), min_size=P,
                                      max_size=P, unique=True))))


@given(z_strategy(), st.integers(1, 100_000))
@settings(max_examples=200, deadline=None)
def test_step_function_simulation(z, f):
    """M(f) equals a direct simulation of the allocation process."""
    slots = 1 << z[0]
    cap_left = 1 << z[0]
    pool = 0
    n_slices = 1
    for _ in range(f - 1 if f else 0):
        pass
    remaining = f - min(f, cap_left)
    while remaining > 0:
        pool = min(pool + 1, len(z) - 1)
        take = (1 << z[pool]) - 1
        slots += (1 << z[pool])
        n_slices += 1
        remaining -= min(remaining, take)
    assert int(an.memory_slots(z, [f])[0]) == slots
    assert int(an.slices_needed(z, [f])[0]) == n_slices
    assert int(an.pointer_count(z, [f])[0]) == n_slices - 1


@given(z_strategy(),
       st.integers(1000, 200_000),   # vocab
       st.floats(0.8, 1.4))          # alpha
@settings(max_examples=30, deadline=None)
def test_closed_form_matches_bruteforce(z, vocab, alpha):
    n_tokens = vocab * 8
    brute = an.memory_cost_bruteforce(z, vocab, n_tokens, alpha)
    closed = an.memory_cost_closed_form(z, vocab, n_tokens, alpha)
    assert closed == pytest.approx(brute, rel=1e-6), (z, vocab, alpha)


def test_paper_scale_closed_form():
    """Paper's fitted parameters: alpha=1.0, |V|=11e6, N=76e6 (§6).
    Production config C_M should land in the paper's ~90m-slot regime
    (Table 1 reports 90.2m on the second corpus half; our |V|,N are the
    full-corpus fits, so we check the order of magnitude and that the
    configuration ORDERING matches Table 1)."""
    cm_prod = an.memory_cost_closed_form(PROD, 11_000_000, 76_000_000, 1.0)
    cm_z2 = an.memory_cost_closed_form((1, 3, 5, 6, 8, 9, 10, 11),
                                       11_000_000, 76_000_000, 1.0)
    cm_z0 = an.memory_cost_closed_form((0, 1, 2, 3, 4, 5, 6, 8),
                                       11_000_000, 76_000_000, 1.0)
    assert 3e7 < cm_prod < 3e8
    # Table 1 ordering: C_M(Z^0) < C_M(Z^2) < C_M(Z^g)
    assert cm_z0 < cm_z2 < cm_prod


def test_time_cost_monotone_in_fragmentation():
    """Smaller slices => more pointer hops => higher C_T."""
    freqs = np.asarray([5, 50, 500, 5000, 50_000])
    small = an.time_cost((0, 1, 2, 3), freqs)
    prod = an.time_cost(PROD, freqs)
    big = an.time_cost((2, 6, 9, 12), freqs)
    assert small > prod > big


def test_memory_slots_sp_reduces_to_default():
    f = np.asarray([1, 7, 100, 4000])
    assert np.array_equal(an.memory_slots_sp(PROD, f, 0),
                          an.memory_slots(PROD, f))


def test_config_space_counts():
    cfgs = list(an.config_space(slice_range=(0, 5), pools_range=(4, 4)))
    # C(6,4) = 15 strictly-increasing 4-subsets of {0..5}
    assert len(cfgs) == 15
    assert all(len(c) == 4 and list(c) == sorted(c) for c in cfgs)


def _slices_per_pool_loop(z, freqs, slack, start_pools):
    """Term-by-term walk of the §3.3 progression (the per-pool slice
    demand, written the obvious way)."""
    P = len(z)
    need = np.zeros(P, np.int64)
    for f, p0 in zip(freqs, start_pools):
        if f <= 0:
            continue
        remaining = int(f)
        for p in range(int(p0), P):
            cap = 2 ** z[p] - (1 if p > 0 else 0)
            if p < P - 1:
                need[p] += 1
                remaining -= cap
                if remaining <= 0:
                    break
            else:
                need[p] += max(-(-remaining // cap), 1)
                break
    return tuple(int(x) for x in np.maximum((need * slack).astype(np.int64),
                                            8))


@pytest.mark.parametrize("z", [PROD, (0, 2, 5), (3,), (1, 2, 3, 4, 5)])
@pytest.mark.parametrize("sp", [False, True])
def test_slices_per_pool_matches_term_walk(z, sp):
    rng = np.random.default_rng(len(z) + sp)
    freqs = np.concatenate([rng.zipf(1.3, 2000) % 20000, [0, 1, 2, 3]])
    starts = (rng.integers(0, len(z), freqs.size) if sp
              else np.zeros(freqs.size, np.int64))
    got = an.slices_per_pool(z, freqs, slack=1.3,
                             start_pools=starts if sp else None)
    assert got == _slices_per_pool_loop(z, freqs, 1.3, starts)
