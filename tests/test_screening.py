"""Unit + property tests for the BRIDGE screening rules (paper Sec. III).

Property-style tests enumerate seeded random cases (the environment has no
``hypothesis``; a fixed seed grid keeps them deterministic and CI-stable).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import complete_graph, erdos_renyi, screen_all, screening

RULES = ["trimmed_mean", "median", "krum", "bulyan"]


def _setup(m=15, d=6, b=2, seed=0):
    rng = np.random.default_rng(seed)
    topo = complete_graph(m, b)
    w = jnp.asarray(rng.random((m, d)), jnp.float32)
    return topo, w, rng


@pytest.mark.parametrize("rule", RULES)
def test_hull_invariant(rule):
    """The core robustness property (basis of Eq. 14): honest nodes' screened
    outputs stay inside the convex hull (per-coordinate) of honest values, no
    matter what the <=b Byzantine nodes broadcast."""
    m, b = 15, 2
    topo, w, rng = _setup(m=m, b=b)
    byz = [3, 7]
    w = w.at[3].set(1e4).at[7].set(-1e4)
    honest = np.setdiff1d(np.arange(m), byz)
    hv = np.asarray(w)[honest]
    topo.validate_for_rule(rule)
    y = np.asarray(screen_all(w, jnp.asarray(topo.adjacency), rule=rule, b=b))[honest]
    assert (y >= hv.min(0) - 1e-4).all() and (y <= hv.max(0) + 1e-4).all()


@pytest.mark.parametrize("n,b,seed", [
    (7, 0, 0), (7, 1, 1), (7, 2, 2), (9, 2, 3), (11, 0, 4), (11, 1, 5),
    (12, 2, 6), (13, 1, 7), (14, 2, 8), (15, 0, 9), (15, 1, 10), (15, 2, 11),
])
def test_trimmed_mean_matches_numpy(n, b, seed):
    rng = np.random.default_rng(seed)
    vals = list(rng.uniform(-100, 100, size=n).astype(np.float32))
    v = jnp.asarray(vals, jnp.float32)[:, None]
    mask = jnp.ones((n,), bool)
    self_v = jnp.asarray([0.0], jnp.float32)
    out = screening.trimmed_mean(v, mask, self_v, b)
    s = np.sort(np.asarray(vals, np.float32))
    kept = s[b : n - b] if b else s
    expected = (kept.sum() + 0.0) / (n - 2 * b + 1)
    np.testing.assert_allclose(np.asarray(out)[0], expected, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n,seed", [
    (3, 0), (4, 1), (5, 2), (6, 3), (7, 4), (8, 5), (9, 6), (10, 7),
    (11, 8), (12, 9), (13, 10), (14, 11),
])
def test_median_matches_numpy(n, seed):
    rng = np.random.default_rng(100 + seed)
    vals = list(rng.uniform(-50, 50, size=n).astype(np.float32))
    v = jnp.asarray(vals, jnp.float32)[:, None]
    mask = jnp.ones((n,), bool)
    self_v = jnp.asarray([vals[0]], jnp.float32)
    out = screening.coordinate_median(v, mask, self_v)
    expected = np.median(np.asarray(vals + [vals[0]], np.float32))
    np.testing.assert_allclose(np.asarray(out)[0], expected, rtol=1e-5, atol=1e-5)


def test_trimmed_mean_b0_is_dgd_mean():
    """BRIDGE-T reduces to (uniform-weight) DGD when b=0 (Sec. III)."""
    topo, w, _ = _setup(b=0)
    adj = jnp.asarray(topo.adjacency)
    yt = screen_all(w, adj, rule="trimmed_mean", b=0)
    ym = screen_all(w, adj, rule="mean", b=0)
    np.testing.assert_allclose(np.asarray(yt), np.asarray(ym), rtol=1e-5)


def test_median_affine_equivariance():
    """Rank-based rules commute with positive affine maps per coordinate."""
    topo, w, _ = _setup()
    adj = jnp.asarray(topo.adjacency)
    a, c = 2.5, -1.0
    y1 = screen_all(a * w + c, adj, rule="median", b=2)
    y2 = a * screen_all(w, adj, rule="median", b=2) + c
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-4, atol=1e-4)


def test_krum_selects_inlier():
    """Krum must never output the obvious outlier vector."""
    m, b = 10, 1
    topo = complete_graph(m, b)
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.normal(0, 0.1, (m, 8)), jnp.float32)
    w = w.at[4].set(50.0)
    y = np.asarray(screen_all(w, jnp.asarray(topo.adjacency), rule="krum", b=b))
    honest = [i for i in range(m) if i != 4]
    assert np.abs(y[honest]).max() < 1.0


def test_varying_degrees_masked_correctly():
    """ER graph (varying |N_j|): output dims/finiteness + hull invariant."""
    topo = erdos_renyi(12, 0.8, 2, seed=3)
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.random((12, 5)), jnp.float32)
    for rule in ["trimmed_mean", "median"]:
        y = np.asarray(screen_all(w, jnp.asarray(topo.adjacency), rule=rule, b=2))
        assert np.isfinite(y).all()
        assert (y >= 0 - 1e-5).all() and (y <= 1 + 1e-5).all()


def test_chunked_screening_matches():
    topo, w, _ = _setup(d=137)
    adj = jnp.asarray(topo.adjacency)
    full = screen_all(w, adj, rule="trimmed_mean", b=2)
    chunked = screen_all(w, adj, rule="trimmed_mean", b=2, chunk=32)
    np.testing.assert_allclose(np.asarray(full), np.asarray(chunked), rtol=1e-5)


# ---------------------------------------------------------------------------
# Extreme magnitudes: the inf-sentinel regression suite
# ---------------------------------------------------------------------------
#
# The old masking used a finite 1e30 sentinel: any legitimate value beyond it
# (fp32 goes to 3.4e38; bf16 overflow products routinely land there) sorted
# *past* the sentinel rows, so masked slots leaked into the trim window and
# silently corrupted the output.  Masking is now +inf with a NaN guard.


def test_trimmed_mean_huge_honest_values_not_corrupted():
    """Honest values in the 1e31..1e32 range (beyond the old sentinel) must
    still produce the exact trimmed mean."""
    n, b = 9, 2
    rng = np.random.default_rng(0)
    vals = (rng.uniform(1.0, 9.0, size=n) * 1e31).astype(np.float32)
    v = jnp.asarray(vals)[:, None]
    mask = jnp.ones((n,), bool)
    self_v = jnp.asarray([np.float32(5e31)])
    out = float(np.asarray(screening.trimmed_mean(v, mask, self_v, b))[0])
    s = np.sort(vals.astype(np.float64))
    expected = (s[b: n - b].sum() + 5e31) / (n - 2 * b + 1)
    np.testing.assert_allclose(out, expected, rtol=1e-5)


def test_trimmed_mean_extreme_attack_values_trimmed():
    """A colluding attacker broadcasting 1e38 / -1e38 / +-inf payloads is
    fully trimmed; honest values survive untouched."""
    m, b, d = 11, 2, 3
    honest_vals = np.linspace(1.0, 7.0, m - b).astype(np.float32)
    for bad in (3.4e38, -3.4e38, np.inf, -np.inf):
        vals = np.concatenate([honest_vals, np.full((b,), bad, np.float32)])
        v = jnp.asarray(np.broadcast_to(vals[:, None], (m, d)).copy())
        out = np.asarray(screening.trimmed_mean(v, jnp.ones((m,), bool),
                                                jnp.full((d,), 4.0, jnp.float32), b))
        assert np.isfinite(out).all(), f"attack value {bad} leaked"
        expected = (np.sort(vals.astype(np.float64))[b: m - b].sum() + 4.0) / (m - 2 * b + 1)
        np.testing.assert_allclose(out, expected, rtol=1e-5)


def test_median_huge_magnitudes_exact():
    n = 8
    vals = np.array([1e31, 2e31, 3e31, -4e31, 5e31, 2.5e31, 1.5e31, 4e31], np.float32)
    v = jnp.asarray(vals)[:, None]
    out = float(np.asarray(screening.coordinate_median(v, jnp.ones((n,), bool),
                                                       jnp.asarray([2.2e31], jnp.float32)))[0])
    expected = float(np.median(np.concatenate([vals, [np.float32(2.2e31)]]).astype(np.float64)))
    np.testing.assert_allclose(out, expected, rtol=1e-6)


def test_nan_payloads_guarded():
    """NaN payloads (the finite-count guard) are treated as maximal outliers:
    trimmed away, never propagated into honest outputs."""
    m, b, d = 11, 2, 4
    vals = np.linspace(-2.0, 2.0, m).astype(np.float32)
    v = np.broadcast_to(vals[:, None], (m, d)).copy()
    v[3] = np.nan
    v[7] = np.nan
    out_t = np.asarray(screening.trimmed_mean(jnp.asarray(v), jnp.ones((m,), bool),
                                              jnp.zeros((d,), jnp.float32), b))
    out_m = np.asarray(screening.coordinate_median(jnp.asarray(v), jnp.ones((m,), bool),
                                                   jnp.zeros((d,), jnp.float32)))
    assert np.isfinite(out_t).all() and np.isfinite(out_m).all()
    honest = np.delete(vals, [3, 7])
    assert (out_t >= honest.min() - 1e-5).all() and (out_t <= honest.max() + 1e-5).all()


def test_hull_invariant_under_extreme_attack():
    """Eq. 14's hull property holds even when the attack magnitude dwarfs the
    old finite sentinel."""
    m, b = 15, 2
    topo, w, _ = _setup(m=m, b=b)
    w = w.at[3].set(2.9e38).at[7].set(-2.9e38)
    honest = np.setdiff1d(np.arange(m), [3, 7])
    hv = np.asarray(w)[honest]
    for rule in ("trimmed_mean", "median"):
        y = np.asarray(screen_all(w, jnp.asarray(topo.adjacency), rule=rule, b=b))[honest]
        assert (y >= hv.min(0) - 1e-4).all() and (y <= hv.max(0) + 1e-4).all()


# The 2-D forms of the coordinate-wise rules as they were before the rules
# took values of any trailing shape.  The flat, stream, grid and net paths
# call the rules on ``[n, d]`` values, and their programs must keep these ops.
def _sort_rows_2d(values):
    rows = list(values)
    for a, b in screening._batcher_pairs(values.shape[0]):
        rows[a], rows[b] = jnp.minimum(rows[a], rows[b]), jnp.maximum(rows[a], rows[b])
    return jnp.stack(rows)


def _trimmed_mean_2d(values, mask, self_value, b):
    n = values.shape[0]
    count = jnp.sum(mask)
    b_eff = screening.effective_trim(b, count)
    masked = jnp.where(mask[:, None], screening._sanitize(values), screening._MASKED)
    order = _sort_rows_2d(masked)
    idx = jnp.arange(n)[:, None]
    keep = (idx >= b_eff) & (idx < count - b_eff)
    total = screening.sum_rows(jnp.where(keep, order, 0.0)) + self_value
    y = total / (count - 2 * b_eff + 1).astype(values.dtype)
    anchor = jnp.min(order)
    return jnp.where(anchor == anchor, y, jnp.zeros_like(y))


def _median_2d(values, mask, self_value, b):
    stacked = jnp.concatenate([values, self_value[None, :]], axis=0)
    full_mask = jnp.concatenate([mask, jnp.ones((1,), dtype=bool)], axis=0)
    count = jnp.sum(full_mask)
    order = _sort_rows_2d(jnp.where(full_mask[:, None], screening._sanitize(stacked),
                                    screening._MASKED))
    lo, hi = (count - 1) // 2, count // 2
    idx = jnp.arange(stacked.shape[0])[:, None]
    pick_lo = jnp.sum(jnp.where(idx == lo, order, 0.0), axis=0)
    pick_hi = jnp.sum(jnp.where(idx == hi, order, 0.0), axis=0)
    return screening.fence(0.5 * (pick_lo + pick_hi))


def _mean_2d(values, mask, self_value, b):
    count = jnp.sum(mask)
    total = screening.sum_rows(jnp.where(mask[:, None], values, 0.0)) + self_value
    return total / (count + 1).astype(values.dtype)


def _trimmed_mean_with_decisions_2d(values, mask, self_value, b):
    n = values.shape[0]
    count = jnp.sum(mask)
    b_eff = screening.effective_trim(b, count)
    masked = jnp.where(mask[:, None], screening._sanitize(values), screening._MASKED)
    order = _sort_rows_2d(masked)
    idx = jnp.arange(n)[:, None]
    keep = (idx >= b_eff) & (idx < count - b_eff)
    total = screening.sum_rows(jnp.where(keep, order, 0.0)) + self_value
    y = total / (count - 2 * b_eff + 1).astype(values.dtype)
    lo = jax.lax.dynamic_index_in_dim(order, b_eff, 0, keepdims=False)
    hi = jax.lax.dynamic_index_in_dim(
        order, jnp.maximum(count - b_eff - 1, b_eff), 0, keepdims=False)
    kept = (masked[:, ::1] >= lo[None, ::1]) & (masked[:, ::1] <= hi[None, ::1])
    trim = jnp.mean((mask[:, None] & ~kept).astype(jnp.float32), axis=1)
    anchor = jnp.min(order)
    return y, jnp.where(anchor == anchor, trim, jnp.zeros_like(trim))


@pytest.mark.parametrize("rule,before", [
    (screening.trimmed_mean, _trimmed_mean_2d),
    (screening.coordinate_median, _median_2d),
    (screening.mean, _mean_2d),
    (screening.trimmed_mean_with_decisions, _trimmed_mean_with_decisions_2d),
])
def test_2d_rules_keep_their_ops(rule, before):
    """On ``[n, d]`` values each coordinate-wise rule traces to exactly the
    program of its 2-D form, anchor and fence included."""
    args = (jax.ShapeDtypeStruct((7, 13), jnp.float32), jax.ShapeDtypeStruct((7,), bool),
            jax.ShapeDtypeStruct((13,), jnp.float32))
    got = jax.make_jaxpr(lambda v, m, s: rule(v, m, s, 2))(*args)
    want = jax.make_jaxpr(lambda v, m, s: before(v, m, s, 2))(*args)
    assert str(got) == str(want)
