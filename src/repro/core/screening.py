"""Screening rules of the BRIDGE framework (Sec. III, Table II).

All rules share the signature::

    screen(values, mask, self_value, b) -> y

where ``values`` is ``[n, d]`` — the messages received from (up to) ``n``
potential in-neighbors, ``mask`` is ``[n]`` bool marking which rows are real
neighbors (graphs have varying degree; rows with ``mask==False`` are ignored),
``self_value`` is ``[d]`` — the node's own iterate, and ``b`` is the maximum
number of Byzantine nodes to tolerate.

These are the pure-jnp reference implementations; `repro.kernels` provides the
Pallas TPU realizations of the coordinate-wise hot loops, and `gossip.py`
applies these rules on parameter shards under shard_map.

Numerics note: trimmed-mean / median are rank-based, so they are invariant to
any monotone per-coordinate transform of the Byzantine entries — the basis of
the paper's resilience argument (Eq. 14: every surviving Byzantine value is a
convex combination of honest values).

Masked entries use a ``+inf`` sentinel, NOT a large finite constant: a finite
sentinel silently corrupts the rank windows whenever legitimate (or attacked)
values exceed it — e.g. fp32 payloads in the 1e30..3e38 range, or bf16
overflow products — because data then sorts *past* the sentinel rows.  With
``+inf`` every finite value ranks strictly before the sentinels.  Non-finite
*payloads* still rank correctly (-inf trims from the bottom, +inf from the
top); NaN payloads would poison the sort order and are explicitly guarded to
``+inf`` so they are trimmed with the other top-magnitude outliers.
"""
from __future__ import annotations

import functools
from collections.abc import Callable, Sequence

import jax
import jax.numpy as jnp

_MASKED = jnp.inf  # sentinel for masked entries; see module docstring


def sum_rows(x: jax.Array) -> jax.Array:
    """Strictly sequential sum over the leading (neighbor) axis.

    ``jnp.sum`` lowers to a shape-dependent reduction tree, so summing the
    same non-zero rows padded to *different* row counts can differ in ULPs —
    which would break the dense [M]-row vs sparse [K]-row screening
    bit-identity contract (`repro.core.neighbors`).  A left-to-right chain is
    layout-invariant: ``x + 0.0`` is exact, so present-but-zeroed padded rows
    drop out bitwise.  ONLY safe when the summand contains no multiply: XLA
    may FMA-contract ``a * b + total`` in one program shape but not the
    other, which is exactly the ULP drift the chain exists to prevent — sums
    over products must use `sum_rows_mat`.  Falls back to ``jnp.sum`` above
    the same row bound as `sort_rows` (a huge-M dense run is the slow
    oracle, not a bit-identity reference).
    """
    n = x.shape[0]
    if n > 64:
        return jnp.sum(x, axis=0)
    total = x[0]
    for i in range(1, n):
        total = total + x[i]
    return total


def sum_rows_mat(x: jax.Array) -> jax.Array:
    """`sum_rows` for summands that contain a product (geomedian's weighted
    rows, clipped-mean's scaled deltas): a ``lax.scan`` *materializes* its
    ``xs`` operand, so the producer multiply is rounded to storage precision
    before the loop and the body is a pure, contraction-proof add.
    (Whether ``optimization_barrier`` would be a cheaper fence here is not
    measured; the scan is part of the bit-identity contract until a chip run
    says otherwise.)"""
    n = x.shape[0]
    if n > 64:
        return jnp.sum(x, axis=0)
    total, _ = jax.lax.scan(lambda tot, row: (tot + row, None), jnp.zeros_like(x[0]), x)
    return total


def fence(x: jax.Array) -> jax.Array:
    """Round ``x`` to storage precision behind a ``lax.scan`` (whose ``xs``
    XLA must materialize).  Rules whose *last* operation is a multiply
    (`coordinate_median`'s ``0.5 * (lo + hi)``) would otherwise leave the
    caller free to FMA-contract that multiply into its own subtract in one
    program shape but not another — the same cross-program ULP drift
    `sum_rows_mat` guards inside the rules.  The scan is length TWO, not
    one: XLA's while-loop simplifier unrolls trip-count-<=1 loops, which
    would re-fuse the producer and void the fence."""
    out, _ = jax.lax.scan(lambda c, row: (row, None), jnp.zeros_like(x),
                          jnp.stack([x, x]))
    return out


def effective_trim(b, count: jax.Array) -> jax.Array:
    """The trim width a ``count``-strong usable neighborhood can support:
    ``min(b, (count - 1) // 2)``.

    `Topology.validate_for_rule` certifies Table II's ``|N_j| >= 2b + 1`` on
    the *static* graph only; a churn/partition schedule (`repro.net.dynamic`)
    can drop a tick's live in-degree below that, where an unclamped trim
    window would sweep ``+inf`` sentinel rows into the kept ranks and the
    divisor ``count - 2b + 1`` through zero.  At or above the bound the clamp
    is the identity (``b_eff == b``) — bit-identical to the unclamped rule —
    and below it the rule degrades to the widest trim the tick supports (the
    network runtime additionally freezes such nodes entirely; this clamp
    covers the paths with no freeze, e.g. the adversary's per-tick screening
    oracle).  Regression-tested in ``tests/test_sparse.py``.
    """
    cnt = jnp.asarray(count, jnp.int32)
    return jnp.clip(jnp.asarray(b, jnp.int32), 0, jnp.maximum((cnt - 1) // 2, 0))


def _sanitize(values: jax.Array) -> jax.Array:
    """NaN payloads -> +inf so rank-based rules treat them as maximal outliers
    (the explicit finite-payload guard for the inf-sentinel masking)."""
    return jnp.where(jnp.isnan(values), _MASKED, values)


@functools.lru_cache(maxsize=None)
def _batcher_pairs(n: int) -> tuple[tuple[int, int], ...]:
    """Batcher odd-even mergesort compare-exchange schedule for n elements
    (works for arbitrary n, ~n/2 log^2 n pairs)."""
    pairs = []
    p = 1
    while p < n:
        k = p
        while k >= 1:
            for j in range(k % p, n - k, 2 * k):
                for i in range(min(k, n - j - k)):
                    if (i + j) // (2 * p) == (i + j + k) // (2 * p):
                        pairs.append((i + j, i + j + k))
            k //= 2
        p *= 2
    return tuple(pairs)


def sort_rows(values: jax.Array) -> jax.Array:
    """Ascending sort of ``values [n, d]`` along the (small) neighbor axis.

    XLA's CPU sort lowers to a scalar per-column loop — ~1us per 12-element
    column, which makes screening the step's hot spot.  For the neighbor
    counts BRIDGE actually sees (n <= a few dozen) a Batcher odd-even merge
    network of element-wise ``minimum``/``maximum`` over whole [d] rows
    vectorizes instead, an order of magnitude faster, and produces the exact
    sorted array (values are unique-by-rank, so the output is identical to
    ``jnp.sort``).  Large n falls back to ``jnp.sort``.  NaNs must already be
    sanitized (min/max would propagate them through the network).
    """
    n = values.shape[0]
    if n > 64:
        return jnp.sort(values, axis=0)
    rows = list(values)
    for a, b in _batcher_pairs(n):
        lo = jnp.minimum(rows[a], rows[b])
        hi = jnp.maximum(rows[a], rows[b])
        rows[a], rows[b] = lo, hi
    return jnp.stack(rows)


# ---------------------------------------------------------------------------
# Coordinate-wise rules (BRIDGE-T, BRIDGE-M)
# ---------------------------------------------------------------------------


def trimmed_mean(values: jax.Array, mask: jax.Array, self_value: jax.Array, b: int) -> jax.Array:
    """BRIDGE-T screening — Eq. (7)-(10).

    Per coordinate k: drop the b largest and b smallest neighbor values, then
    average the survivors together with the node's own value, with divisor
    ``|N_j| - 2b + 1``.
    """
    n = values.shape[0]
    count = jnp.sum(mask)  # |N_j|, traced scalar
    b_eff = effective_trim(b, count)  # == b whenever count >= 2b + 1
    masked = jnp.where(mask[:, None], _sanitize(values), _MASKED)
    order = sort_rows(masked)  # ascending; masked at the end
    idx = jnp.arange(n)[:, None]
    keep = (idx >= b_eff) & (idx < count - b_eff)  # ranks [b_eff, |N_j| - b_eff)
    total = sum_rows(jnp.where(keep, order, 0.0)) + self_value
    y = total / (count - 2 * b_eff + 1).astype(values.dtype)
    # XLA CPU re-computes the fused sort network per consumer; a scalar
    # full-reduce consumer forces `order` to materialize once (~3x on
    # [128, 16, 64]).  min (not sum: huge payloads overflow a sum to
    # inf - inf = NaN) of NaN-sanitized input is never NaN, so the select is
    # the identity bitwise, but the compare can't be constant-folded — that
    # is what keeps the reduce alive.
    anchor = jnp.min(order)
    return jnp.where(anchor == anchor, y, jnp.zeros_like(y))


def coordinate_median(values: jax.Array, mask: jax.Array, self_value: jax.Array, b: int = 0) -> jax.Array:
    """BRIDGE-M screening — Eq. (11): coordinate-wise median over N_j ∪ {j}.

    Even cardinalities average the two middle order statistics.
    """
    del b  # median needs no explicit knowledge of b (Sec. III)
    stacked = jnp.concatenate([values, self_value[None, :]], axis=0)
    full_mask = jnp.concatenate([mask, jnp.ones((1,), dtype=bool)], axis=0)
    n1 = stacked.shape[0]
    count = jnp.sum(full_mask)
    order = sort_rows(jnp.where(full_mask[:, None], _sanitize(stacked), _MASKED))
    lo = (count - 1) // 2
    hi = count // 2
    idx = jnp.arange(n1)[:, None]
    pick_lo = jnp.sum(jnp.where(idx == lo, order, 0.0), axis=0)
    pick_hi = jnp.sum(jnp.where(idx == hi, order, 0.0), axis=0)
    return fence(0.5 * (pick_lo + pick_hi))


# ---------------------------------------------------------------------------
# Vector rules (BRIDGE-K, BRIDGE-B)
# ---------------------------------------------------------------------------


def pairwise_sq_dists(values: jax.Array, mask: jax.Array, self_value: jax.Array):
    """[n+1, n+1] squared distances among neighbors + self (self last row/col).

    Returns (dists, full_mask); masked rows/cols hold +BIG off-diagonal.
    """
    stacked = jnp.concatenate([values, self_value[None, :]], axis=0)
    full_mask = jnp.concatenate([mask, jnp.ones((1,), dtype=bool)], axis=0)
    sq = jnp.sum(stacked * stacked, axis=-1)
    d2 = sq[:, None] + sq[None, :] - 2.0 * (stacked @ stacked.T)
    d2 = jnp.maximum(d2, 0.0)
    valid = full_mask[:, None] & full_mask[None, :]
    d2 = jnp.where(valid, d2, _MASKED)
    return d2, full_mask


def _krum_scores(d2: jax.Array, full_mask: jax.Array, count: jax.Array, b: int) -> jax.Array:
    """Krum score per candidate row of the distance matrix ``d2``.

    score(i) = sum of the (|N_j| - b - 2) smallest distances from i to other
    valid vectors (Eq. 12).  Invalid candidates get +inf scores.
    """
    n1 = d2.shape[0]
    eye = jnp.eye(n1, dtype=bool)
    d2 = jnp.where(eye, _MASKED, d2)  # exclude self-distance
    order = jnp.sort(d2, axis=1)  # ascending per candidate
    k = count - b - 2  # number of nearest peers to sum (traced)
    idx = jnp.arange(n1)[None, :]
    take = idx < jnp.maximum(k, 1)
    # transpose so the (sorted-rank) reduction runs through the
    # layout-invariant sequential chain — see `sum_rows`
    scores = sum_rows(jnp.where(take, order, 0.0).T)
    return jnp.where(full_mask, scores, jnp.inf)


def krum(values: jax.Array, mask: jax.Array, self_value: jax.Array, b: int) -> jax.Array:
    """BRIDGE-K screening — Eq. (12): output the whole vector of the neighbor
    minimizing the Krum score.  Candidates are the neighbors only (i ∈ N_j),
    while distances range over N_j ∪ {j}."""
    d2, full_mask = pairwise_sq_dists(values, mask, self_value)
    count = jnp.sum(mask)  # |N_j|
    scores = _krum_scores(d2, full_mask, count, b)
    cand_scores = jnp.where(mask, scores[:-1], jnp.inf)  # exclude self as candidate
    i_star = jnp.argmin(cand_scores)
    return values[i_star]


def _bulyan_select(values: jax.Array, mask: jax.Array, self_value: jax.Array, b: int) -> jax.Array:
    """Bulyan's recursive-Krum selection mask: the |N_j| - 2b neighbors the
    trimmed-mean stage then aggregates.  Factored out so the decision-
    instrumented twin reuses the exact selection op graph."""
    n = values.shape[0]
    d2, full_mask = pairwise_sq_dists(values, mask, self_value)
    count0 = jnp.sum(mask)
    n_select = count0 - 2 * b  # traced

    def body(step, carry):
        cand_mask, sel_mask = carry
        cnt = jnp.sum(cand_mask)
        fm = jnp.concatenate([cand_mask, jnp.ones((1,), dtype=bool)])
        valid = fm[:, None] & fm[None, :]
        d2s = jnp.where(valid, d2, _MASKED)
        scores = _krum_scores(d2s, fm, cnt, b)
        cand_scores = jnp.where(cand_mask, scores[:-1], jnp.inf)
        i_star = jnp.argmin(cand_scores)
        active = step < n_select
        pick = jnp.zeros((n,), dtype=bool).at[i_star].set(active)
        return cand_mask & ~pick, sel_mask | pick

    _, selected = jax.lax.fori_loop(0, n, body, (mask, jnp.zeros((n,), dtype=bool)))
    return selected


def bulyan(values: jax.Array, mask: jax.Array, self_value: jax.Array, b: int) -> jax.Array:
    """BRIDGE-B screening: recursive-Krum selection of |N_j| - 2b neighbors,
    then coordinate-wise trimmed mean (with self) over the selected set."""
    selected = _bulyan_select(values, mask, self_value, b)
    return trimmed_mean(values, selected, self_value, b)


def geometric_median(values: jax.Array, mask: jax.Array, self_value: jax.Array,
                     b: int = 0, *, iters: int = 8, eps: float = 1e-6) -> jax.Array:
    """Geometric median over N_j ∪ {j} via Weiszfeld iterations — an extra
    BRIDGE variant from the robust-statistics menu the paper points at
    (Sec. III: "additional variants ... from the literature on robust
    statistics").  Breakdown point 1/2; no explicit b needed."""
    del b
    stacked = jnp.concatenate([values, self_value[None, :]], axis=0)
    fm = jnp.concatenate([mask, jnp.ones((1,), bool)], axis=0).astype(values.dtype)
    y = sum_rows_mat(stacked * fm[:, None]) / jnp.sum(fm)

    def body(y, _):
        d = jnp.sqrt(jnp.sum((stacked - y[None]) ** 2, axis=1) + eps)
        w = fm / d
        y = sum_rows_mat(stacked * w[:, None]) / sum_rows(w[:, None])[0]
        return y, None

    y, _ = jax.lax.scan(body, y, None, length=iters)
    return y


def clipped_mean(values: jax.Array, mask: jax.Array, self_value: jax.Array,
                 b: int = 0, *, tau: float = 1.0) -> jax.Array:
    """Centered clipping (Karimireddy et al. style): average of neighbor
    deltas clipped to an l2 ball of radius tau around the node's own iterate.
    Bounds each neighbor's influence by tau/|N_j| per step."""
    del b
    delta = values - self_value[None, :]
    nrm = jnp.sqrt(jnp.sum(delta * delta, axis=1, keepdims=True) + 1e-12)
    scale = jnp.minimum(1.0, tau / nrm)
    clipped = delta * scale
    cnt = jnp.sum(mask)
    return self_value + sum_rows_mat(jnp.where(mask[:, None], clipped, 0.0)) / jnp.maximum(cnt, 1)


def mean(values: jax.Array, mask: jax.Array, self_value: jax.Array, b: int = 0) -> jax.Array:
    """No screening — plain DGD neighbor averaging (uniform weights over
    N_j ∪ {j}).  The b=0 baseline the paper's Figures 1-2 compare against."""
    del b
    count = jnp.sum(mask)
    total = sum_rows(jnp.where(mask[:, None], values, 0.0)) + self_value
    return total / (count + 1).astype(values.dtype)


# ---------------------------------------------------------------------------
# Reputation-aware rules (repro.trust)
# ---------------------------------------------------------------------------
#
# The trust layer carries per-edge reputation weights (``clip(1 - suspicion,
# 0, 1)``, 0 = evicted) and feeds them to these rules through the ``weights``
# keyword of the decide-banked dispatch.  With ``weights=None`` they act with
# uniform weights, so they remain valid standalone registry entries; rules
# outside `WEIGHTED_RULES` simply ignore the weights operand (eviction still
# reaches them through the screening mask).  Because detection-and-eviction
# removes attackers instead of out-voting them, the rep variants advertise a
# weaker MIN_NEIGHBORS requirement (b + 1 instead of 2b + 1) — the degree
# headroom the detect-and-expel breakdown study spends (benchmarks/
# trust_bench.py).


def _rep_trim_window(values, mask, b):
    """Shared kept-window core: boundary order statistics of the masked sort
    (the same dynamic row gathers the decision twins use)."""
    count = jnp.sum(mask)
    b_eff = effective_trim(b, count)
    masked = jnp.where(mask[:, None], _sanitize(values), _MASKED)
    order = sort_rows(masked)
    lo = jax.lax.dynamic_index_in_dim(order, b_eff, 0, keepdims=False)
    hi = jax.lax.dynamic_index_in_dim(
        order, jnp.maximum(count - b_eff - 1, b_eff), 0, keepdims=False)
    kept = mask[:, None] & (masked >= lo[None, :]) & (masked <= hi[None, :])
    return masked, order, kept


def rep_trimmed_mean(values, mask, self_value, b, *, weights=None):
    """Reputation-weighted BRIDGE-T: trim the b largest / b smallest per
    coordinate as usual, then average the survivors with per-edge reputation
    weights (self always weight 1): ``y = (sum_i w_i kept_i v_i + self) /
    (sum_i w_i kept_i + 1)``.  Uniform weights recover a tie-inclusive
    trimmed mean; weight-0 (evicted) edges drop out exactly."""
    n = values.shape[0]
    masked, order, kept = _rep_trim_window(values, mask, b)
    w = jnp.ones((n,), values.dtype) if weights is None else jnp.asarray(
        weights, values.dtype)
    wk = jnp.where(kept, w[:, None], 0.0)
    total = sum_rows_mat(wk * jnp.where(kept, masked, 0.0)) + self_value
    y = total / (sum_rows_mat(wk) + 1.0)
    anchor = jnp.min(order)  # sort-materialization anchor, see trimmed_mean
    return jnp.where(anchor == anchor, y, jnp.zeros_like(y))


def rep_median(values, mask, self_value, b=0, *, weights=None):
    """Reputation-weighted coordinate median: per coordinate, the smallest
    value whose cumulative reputation weight reaches half the total (self
    carries weight 1, masked rows weight 0).  Uniform weights recover the
    lower-median pick of BRIDGE-M."""
    del b
    n1 = values.shape[0] + 1
    stacked = jnp.concatenate([values, self_value[None, :]], axis=0)
    fm = jnp.concatenate([mask, jnp.ones((1,), bool)], axis=0)
    w = (jnp.ones(values.shape[:1], values.dtype) if weights is None
         else jnp.asarray(weights, values.dtype))
    wfull = jnp.concatenate([jnp.where(mask, w, 0.0), jnp.ones((1,), values.dtype)])
    sv = jnp.where(fm[:, None], _sanitize(stacked), _MASKED)
    order_idx = jnp.argsort(sv, axis=0)
    sorted_vals = jnp.take_along_axis(sv, order_idx, axis=0)
    sorted_w = jnp.take_along_axis(
        jnp.broadcast_to(wfull[:, None], (n1,) + sv.shape[1:]), order_idx, axis=0)
    cum = jnp.cumsum(sorted_w, axis=0)
    first = jnp.argmax(cum >= 0.5 * cum[-1][None, :], axis=0)
    return jnp.take_along_axis(sorted_vals, first[None, :], axis=0)[0]


def rep_trimmed_mean_with_decisions(values, mask, self_value, b, *, weights=None,
                                    decide_stride=1):
    n = values.shape[0]
    masked, order, kept = _rep_trim_window(values, mask, b)
    w = jnp.ones((n,), values.dtype) if weights is None else jnp.asarray(
        weights, values.dtype)
    wk = jnp.where(kept, w[:, None], 0.0)
    total = sum_rows_mat(wk * jnp.where(kept, masked, 0.0)) + self_value
    y = total / (sum_rows_mat(wk) + 1.0)
    s = decide_stride
    trim = jnp.mean((mask[:, None] & ~kept[:, ::s]).astype(jnp.float32), axis=1)
    anchor = jnp.min(order)
    y = jnp.where(anchor == anchor, y, jnp.zeros_like(y))
    trim = jnp.where(anchor == anchor, trim, jnp.zeros_like(trim))
    return y, trim


def rep_median_with_decisions(values, mask, self_value, b=0, *, weights=None,
                              decide_stride=1):
    y = rep_median(values, mask, self_value, b, weights=weights)
    # trim membership mirrors coordinate_median_with_decisions: a value
    # "survives" when it sits inside the (unweighted) middle-rank window of
    # the stacked values — what feeds suspicion is who keeps landing in the
    # tails, which is a rank property independent of the weights
    stacked = jnp.concatenate([values, self_value[None, :]], axis=0)
    full_mask = jnp.concatenate([mask, jnp.ones((1,), dtype=bool)], axis=0)
    n1 = stacked.shape[0]
    count = jnp.sum(full_mask)
    masked = jnp.where(full_mask[:, None], _sanitize(stacked), _MASKED)
    order = sort_rows(masked)
    lo = (count - 1) // 2
    hi = count // 2
    idx = jnp.arange(n1)[:, None]
    pick_lo = jnp.sum(jnp.where(idx == lo, order, 0.0), axis=0)
    pick_hi = jnp.sum(jnp.where(idx == hi, order, 0.0), axis=0)
    s = decide_stride
    kept = (masked[:, ::s] >= pick_lo[None, ::s]) & (masked[:, ::s] <= pick_hi[None, ::s])
    trim = jnp.mean((full_mask[:, None] & ~kept).astype(jnp.float32), axis=1)
    return y, trim[:-1]


# Rules that consume per-edge reputation weights (the rest ignore the
# operand; eviction still reaches them through the screening mask).
WEIGHTED_RULES: frozenset = frozenset({"rep_trimmed_mean", "rep_median"})


# The screening-rule registry.  Names here are what `--rules`, ExperimentGrid
# and the banked lax.switch dispatch resolve; adding a rule means adding an
# entry in each of: RULES, MIN_NEIGHBORS (its Table-II degree requirement —
# `rep_*` rules advertise b + 1, backed by trust-layer eviction rather than
# out-voting), RULES_WITH_DECISIONS if it can report per-edge trim decisions
# (repro.obs forensics), and WEIGHTED_RULES if it consumes reputation
# weights.  Every rule takes masked `[n, d]` neighbor values (absent rows
# carry the +inf sentinel) and must stay total-ordered under inf/NaN decode
# garbage — see docs/ARCHITECTURE.md ("bridge.screen") for where this runs.
RULES: dict[str, Callable] = {
    "trimmed_mean": trimmed_mean,
    "median": coordinate_median,
    "krum": krum,
    "bulyan": bulyan,
    "geomedian": geometric_median,
    "clipped_mean": clipped_mean,
    "mean": mean,
    "rep_trimmed_mean": rep_trimmed_mean,
    "rep_median": rep_median,
}


# ---------------------------------------------------------------------------
# Decision-instrumented twins (screening forensics — repro.obs)
# ---------------------------------------------------------------------------
#
# Each `<rule>_with_decisions` returns ``(y, trim_frac)`` where ``y`` is built
# from the *identical op graph* as the plain rule (bitwise-equal outputs —
# property-tested in tests/test_obs.py, the trace-inertness contract) and
# ``trim_frac[i]`` is the fraction of coordinates on which neighbor i's value
# was excluded from the aggregate (0/1 for the vector rules).  Decisions are
# derived from order statistics the rule already computes — kept-boundary
# thresholds instead of O(n^2 d) per-coordinate rank matrices — so the obs
# path stays inside the <10% overhead budget at M=512.


def trimmed_mean_with_decisions(values, mask, self_value, b, *, decide_stride=1):
    n = values.shape[0]
    count = jnp.sum(mask)
    b_eff = effective_trim(b, count)
    masked = jnp.where(mask[:, None], _sanitize(values), _MASKED)
    order = sort_rows(masked)
    idx = jnp.arange(n)[:, None]
    keep = (idx >= b_eff) & (idx < count - b_eff)
    total = sum_rows(jnp.where(keep, order, 0.0)) + self_value
    y = total / (count - 2 * b_eff + 1).astype(values.dtype)
    # kept iff the value lies within the kept-rank boundary order statistics
    # (ties at the boundary count as kept — conservative for the counters).
    # The picks are dynamic row gathers, not masked reductions: on the
    # anchor-materialized `order` they read 2 rows instead of sweeping all of
    # [n, d] twice — the difference between +6% and +96% step overhead at
    # d=7850.  decide_stride > 1 estimates the per-edge fractions on every
    # stride-th coordinate: sort and boundary picks stay exact, only the
    # O(n*d) membership pass shrinks — the counters' ranking signal
    # accumulates over ticks either way
    s = decide_stride
    lo = jax.lax.dynamic_index_in_dim(order, b_eff, 0, keepdims=False)
    hi = jax.lax.dynamic_index_in_dim(
        order, jnp.maximum(count - b_eff - 1, b_eff), 0, keepdims=False)
    kept = (masked[:, ::s] >= lo[None, ::s]) & (masked[:, ::s] <= hi[None, ::s])
    trim = jnp.mean((mask[:, None] & ~kept).astype(jnp.float32), axis=1)
    # XLA CPU re-computes the fused sort network once per consumer; a scalar
    # full-reduce consumer forces `order` to materialize exactly once, making
    # every other read of it free (measured 5-6x on [128, 16, 64]).  min (not
    # sum, which huge payloads overflow to inf - inf = NaN) of NaN-sanitized
    # input is never NaN, so the select is the identity bitwise — but the
    # compare can't be constant-folded, which keeps the reduce alive.
    anchor = jnp.min(order)
    trim = jnp.where(anchor == anchor, trim, jnp.zeros_like(trim))
    return y, trim


def coordinate_median_with_decisions(values, mask, self_value, b=0, *, decide_stride=1):
    del b
    stacked = jnp.concatenate([values, self_value[None, :]], axis=0)
    full_mask = jnp.concatenate([mask, jnp.ones((1,), dtype=bool)], axis=0)
    n1 = stacked.shape[0]
    count = jnp.sum(full_mask)
    masked = jnp.where(full_mask[:, None], _sanitize(stacked), _MASKED)
    order = sort_rows(masked)
    lo = (count - 1) // 2
    hi = count // 2
    idx = jnp.arange(n1)[:, None]
    pick_lo = jnp.sum(jnp.where(idx == lo, order, 0.0), axis=0)
    pick_hi = jnp.sum(jnp.where(idx == hi, order, 0.0), axis=0)
    y = fence(0.5 * (pick_lo + pick_hi))
    # a value "survives" the median when it sits inside [lo, hi] — i.e. it is
    # one of the middle order statistics the output averages (decide_stride
    # samples the membership pass; see trimmed_mean_with_decisions)
    s = decide_stride
    kept = (masked[:, ::s] >= pick_lo[None, ::s]) & (masked[:, ::s] <= pick_hi[None, ::s])
    trim = jnp.mean((full_mask[:, None] & ~kept).astype(jnp.float32), axis=1)
    return y, trim[:-1]  # drop the self row: decisions are about neighbors


def krum_with_decisions(values, mask, self_value, b, *, decide_stride=1):
    del decide_stride  # whole-vector decision
    n = values.shape[0]
    d2, full_mask = pairwise_sq_dists(values, mask, self_value)
    count = jnp.sum(mask)
    scores = _krum_scores(d2, full_mask, count, b)
    cand_scores = jnp.where(mask, scores[:-1], jnp.inf)
    i_star = jnp.argmin(cand_scores)
    trim = (mask & (jnp.arange(n) != i_star)).astype(jnp.float32)
    return values[i_star], trim


def bulyan_with_decisions(values, mask, self_value, b, *, decide_stride=1):
    selected = _bulyan_select(values, mask, self_value, b)
    y, trim_inner = trimmed_mean_with_decisions(values, selected, self_value, b,
                                                decide_stride=decide_stride)
    # deselected-by-Krum neighbors are fully trimmed; the rest carry the
    # inner trimmed-mean's per-coordinate fractions
    return y, jnp.where(mask & ~selected, 1.0, trim_inner)


def geometric_median_with_decisions(values, mask, self_value, b=0, *,
                                    iters: int = 8, eps: float = 1e-6,
                                    decide_stride=1):
    del decide_stride  # whole-vector decision
    y = geometric_median(values, mask, self_value, b, iters=iters, eps=eps)
    # soft suspicion: distance to the median, normalized by the masked median
    # distance (Weiszfeld downweights rows by 1/distance, so this is the
    # influence deficit); 0 for rows at/inside the typical radius
    n = values.shape[0]
    diff = values - y[None, :]
    dist = jnp.sqrt(jnp.sum(diff * diff, axis=1) + eps)
    cnt = jnp.sum(mask)
    order = jnp.sort(jnp.where(mask, dist, jnp.inf))
    idx = jnp.arange(n)
    lo = jnp.maximum((cnt - 1) // 2, 0)
    hi = jnp.maximum(cnt // 2, 0)
    med = 0.5 * (jnp.sum(jnp.where(idx == lo, order, 0.0))
                 + jnp.sum(jnp.where(idx == hi, order, 0.0)))
    trim = jnp.where(mask, jnp.clip(1.0 - med / jnp.maximum(dist, 1e-12), 0.0, 1.0), 0.0)
    return y, trim.astype(jnp.float32)


def clipped_mean_with_decisions(values, mask, self_value, b=0, *, tau: float = 1.0,
                                decide_stride=1):
    del decide_stride  # whole-vector decision
    y = clipped_mean(values, mask, self_value, b, tau=tau)
    delta = values - self_value[None, :]
    nrm = jnp.sqrt(jnp.sum(delta * delta, axis=1) + 1e-12)
    # clipped = influence capped at tau/|N_j| — the rule's trim analogue
    trim = (mask & (nrm > tau)).astype(jnp.float32)
    return y, trim


def mean_with_decisions(values, mask, self_value, b=0, *, decide_stride=1):
    del decide_stride
    return mean(values, mask, self_value, b), jnp.zeros(values.shape[:1], jnp.float32)


RULES_WITH_DECISIONS: dict[str, Callable] = {
    "trimmed_mean": trimmed_mean_with_decisions,
    "median": coordinate_median_with_decisions,
    "krum": krum_with_decisions,
    "bulyan": bulyan_with_decisions,
    "geomedian": geometric_median_with_decisions,
    "clipped_mean": clipped_mean_with_decisions,
    "mean": mean_with_decisions,
    "rep_trimmed_mean": rep_trimmed_mean_with_decisions,
    "rep_median": rep_median_with_decisions,
}


def get_rule(name: str) -> Callable:
    try:
        return RULES[name]
    except KeyError:
        raise ValueError(
            f"unknown screening rule {name!r}; options: {sorted(RULES)}") from None


# Minimum in-neighborhood size each rule needs to tolerate b Byzantine nodes
# (Table II).  Shared by `graph.Topology.validate_for_rule` and the network
# runtime, which falls back to the node's own iterate whenever fewer usable
# (arrived, fresh) messages are available at a tick.
MIN_NEIGHBORS: dict[str, Callable[[int], int]] = {
    "trimmed_mean": lambda b: 2 * b + 1,
    "median": lambda b: 1,
    "krum": lambda b: b + 3,
    "bulyan": lambda b: max(4 * b, 3 * b + 2) + 1,
    "geomedian": lambda b: 2 * b + 1,
    "clipped_mean": lambda b: 1,
    "mean": lambda b: 0,
    # detect-and-expel variants: eviction removes attackers instead of
    # out-voting them, so the static degree requirement relaxes to b + 1
    # honest-majority headroom (the trust breakdown study's premise)
    "rep_trimmed_mean": lambda b: b + 1,
    "rep_median": lambda b: 1,
}


def min_neighbors(rule: str, b: int) -> int:
    try:
        return MIN_NEIGHBORS[rule](b)
    except KeyError:
        raise ValueError(
            f"unknown screening rule {rule!r}; options: {sorted(MIN_NEIGHBORS)}") from None


# Traceable twins of MIN_NEIGHBORS: ``b`` may be a traced int32 scalar (the
# batched grid engine carries the Byzantine bound as per-experiment data), so
# Python ``max`` is replaced by ``jnp.maximum`` and constants are anchored to
# ``b`` to keep every branch shape/dtype-uniform under ``lax.switch``.
_MIN_NEIGHBORS_TRACEABLE: dict[str, Callable] = {
    "trimmed_mean": lambda b: 2 * b + 1,
    "median": lambda b: 0 * b + 1,
    "krum": lambda b: b + 3,
    "bulyan": lambda b: jnp.maximum(4 * b, 3 * b + 2) + 1,
    "geomedian": lambda b: 2 * b + 1,
    "clipped_mean": lambda b: 0 * b + 1,
    "mean": lambda b: 0 * b,
    "rep_trimmed_mean": lambda b: b + 1,
    "rep_median": lambda b: 0 * b + 1,
}


def min_neighbors_banked(rules: Sequence[str], rule_idx, b) -> jax.Array:
    """Table-II minimum usable in-neighborhood for the rule selected by the
    traced index ``rule_idx`` into the static bank ``rules``; ``b`` may be a
    traced int32 scalar."""
    fns = [_MIN_NEIGHBORS_TRACEABLE[r] for r in rules]
    bi = jnp.asarray(b, jnp.int32)
    if len(fns) == 1:
        return jnp.asarray(fns[0](bi), jnp.int32)
    branches = [lambda bb, fn=fn: jnp.asarray(fn(bb), jnp.int32) for fn in fns]
    return jax.lax.switch(rule_idx, branches, bi)


# ---------------------------------------------------------------------------
# Network-wide application (simulation path, single host)
# ---------------------------------------------------------------------------


def _streams(rule: str, d: int, chunk: int | None) -> bool:
    """True when coordinate streaming engages: then the node axis must be
    iterated sequentially (lax.map) to keep peak memory at [n, chunk] per
    node instead of vmap's [M, n, chunk]."""
    return rule not in ("krum", "bulyan") and chunk is not None and d > chunk


# Rules whose output on a coordinate block equals the same block sliced out of
# the full-d output — the block-streaming contract of `repro.stream`.  This is
# strictly stronger than what `_streams` gates: geomedian's Weiszfeld weights
# and clipped_mean's clipping radii are functions of *full-vector* norms, so
# chunked evaluation changes their result (only tolerable inside `_apply_rule`
# because the default ``screen_chunk`` exceeds every experiment's d); the
# rules here are purely per-coordinate, so block results are bitwise equal.
STREAMABLE_RULES: frozenset = frozenset(
    {"trimmed_mean", "median", "mean", "rep_trimmed_mean", "rep_median"})

# The complement, spelled out rather than computed: `repro.analysis.lint`
# asserts {STREAMABLE_RULES, STREAM_REJECTED_RULES} is an exact partition of
# RULES, so adding a rule forces an explicit streamability decision — a rule
# left out of both sets is a lint failure, not a silent default.
STREAM_REJECTED_RULES: frozenset = frozenset(
    {"krum", "bulyan", "geomedian", "clipped_mean"})


def check_streamable(rules: Sequence[str]) -> None:
    """Raise for rules whose blockwise result differs from the full-d result
    (`repro.stream` refuses them instead of silently changing the rule)."""
    bad = [r for r in rules if r not in STREAMABLE_RULES]
    if bad:
        raise ValueError(
            f"rules {bad} are not coordinate-decomposable and cannot stream "
            f"over parameter blocks (repro.stream); streamable rules: "
            f"{sorted(STREAMABLE_RULES)}")


def _apply_rule(fn, rule, values, mask_j, self_j, b, chunk):
    """One node's screening over its received value matrix ``values [n, d]``,
    optionally streaming coordinate-wise rules over chunks of the coordinate
    dimension (bounding peak memory at ``[n, chunk]`` intermediates per node).
    Shared by `screen_all` (one broadcast matrix for everyone) and
    `screen_views` (per-node mailbox views) so the two paths are numerically
    identical."""
    d = values.shape[1]
    if rule in ("krum", "bulyan") or chunk is None or d <= chunk:
        return fn(values, mask_j, self_j, b)
    # coordinate-wise rules can stream over coordinate chunks
    pad = (-d) % chunk
    wp = jnp.pad(values, ((0, 0), (0, pad)))
    sp = jnp.pad(self_j, (0, pad))
    nchunks = wp.shape[1] // chunk
    wc = wp.reshape(values.shape[0], nchunks, chunk).transpose(1, 0, 2)
    sc = sp.reshape(nchunks, chunk)
    out = jax.lax.map(lambda vs: fn(vs[0], mask_j, vs[1], b), (wc, sc))
    return out.reshape(-1)[:d]


@functools.partial(jax.jit, static_argnames=("rule", "b", "chunk"))
def screen_all(
    w: jax.Array,
    adjacency: jax.Array,
    *,
    rule: str,
    b: int,
    chunk: int | None = None,
) -> jax.Array:
    """Apply a screening rule at every node: ``w`` is ``[M, d]`` stacked node
    iterates (Byzantine rows already substituted by the attack model —
    Definition 1 concerns what nodes *broadcast*), ``adjacency[j, i]`` marks i
    as an in-neighbor of j.  Returns the ``[M, d]`` screened outputs y_j.

    Nodes are screened via ``vmap`` (one fused program over the node axis —
    a sequential ``lax.map`` pays ~ms of while-loop overhead per node on
    CPU).  When ``chunk`` engages (coordinate-wise rule, d > chunk), nodes
    fall back to a sequential ``lax.map`` so peak intermediates stay at
    ``[n, chunk]`` per node — the memory contract huge-d training relies on.
    """
    fn = get_rule(rule)

    def per_node(mask_j, self_j):
        return _apply_rule(fn, rule, w, mask_j, self_j, b, chunk)

    if _streams(rule, w.shape[1], chunk):
        return jax.lax.map(lambda args: per_node(*args), (adjacency, w))
    return jax.vmap(per_node)(adjacency, w)


@functools.partial(jax.jit, static_argnames=("rule", "b", "chunk"))
def screen_views(
    views: jax.Array,
    mask: jax.Array,
    self_vals: jax.Array,
    *,
    rule: str,
    b: int,
    chunk: int | None = None,
) -> jax.Array:
    """Apply a screening rule at every node over *per-node* value views.

    Unlike `screen_all`, where every node screens rows of one shared broadcast
    matrix, here node j screens its own ``views[j] [M, d]`` — e.g. mailbox
    contents delivered by an unreliable network (`repro.net`), where different
    nodes hold different (possibly stale) versions of a sender's iterate and a
    Byzantine sender may have told different receivers different things.
    ``mask[j, i]`` marks the (j, i) entry as usable (arrived and fresh);
    ``self_vals[j]`` is node j's own iterate.  Returns ``[M, d]`` outputs y_j.
    """
    fn = get_rule(rule)

    def per_node(view_j, mask_j, self_j):
        return _apply_rule(fn, rule, view_j, mask_j, self_j, b, chunk)

    if _streams(rule, views.shape[-1], chunk):
        return jax.lax.map(lambda args: per_node(*args), (views, mask, self_vals))
    return jax.vmap(per_node)(views, mask, self_vals)


# ---------------------------------------------------------------------------
# Banked (branchless) dispatch — the batched-grid hot path
# ---------------------------------------------------------------------------
#
# The grid engine runs E experiments with *different* rules inside one jitted
# program, so rule selection cannot be a Python-level ``get_rule``: it is a
# ``lax.switch`` over a static bank of rule names, indexed by a traced int32.
# Under ``vmap`` the switch lowers to "compute every bank entry, select one"
# — branchless, one compilation, no per-cell retracing.  Banks should
# therefore contain only the distinct rules a grid actually uses.  With a
# single-entry bank these degenerate to exactly `screen_all` / `screen_views`
# (the switch is elided), which is how `BridgeTrainer` calls them — keeping
# the per-experiment and batched paths bit-identical.


def _rule_branch(rule: str, chunk):
    fn = get_rule(rule)

    def run(values_per_node, mask_per_node, self_vals, b):
        def per_node(values_j, mask_j, self_j):
            return _apply_rule(fn, rule, values_j, mask_j, self_j, b, chunk)

        if _streams(rule, values_per_node.shape[-1], chunk):
            return jax.lax.map(lambda args: per_node(*args),
                               (values_per_node, mask_per_node, self_vals))
        return jax.vmap(per_node)(values_per_node, mask_per_node, self_vals)

    return run


def _rule_branch_broadcast(rule: str, chunk):
    # like _rule_branch, but every node screens rows of ONE shared matrix —
    # closed over, never materialized per node, so the streaming path keeps
    # its O(M*d + n*chunk) peak instead of an [M, M, d] broadcast
    fn = get_rule(rule)

    def run(w, adjacency, b, self_vals):
        def per_node(mask_j, self_j):
            return _apply_rule(fn, rule, w, mask_j, self_j, b, chunk)

        if _streams(rule, w.shape[1], chunk):
            return jax.lax.map(lambda args: per_node(*args), (adjacency, self_vals))
        return jax.vmap(per_node)(adjacency, self_vals)

    return run


def screen_all_banked(
    w: jax.Array,
    adjacency: jax.Array,
    rules: Sequence[str],
    rule_idx,
    b,
    *,
    chunk: int | None = None,
    self_vals: jax.Array | None = None,
) -> jax.Array:
    """`screen_all` with the rule chosen by a traced ``rule_idx`` into the
    static ``rules`` bank and a (possibly traced) Byzantine bound ``b``.

    ``self_vals`` separates the matrix nodes *screen* (``w`` — what arrived,
    e.g. decoded wire codewords) from the value each node combines as its own
    (``self_vals[j]`` — its local iterate, which never travels the wire and
    is never compressed).  Defaults to ``w`` itself, the classic broadcast
    semantics where both coincide."""
    if self_vals is None:
        self_vals = w
    branches = [_rule_branch_broadcast(r, chunk) for r in rules]
    if len(branches) == 1:
        return branches[0](w, adjacency, b, self_vals)
    return jax.lax.switch(rule_idx, branches, w, adjacency, b, self_vals)


def screen_views_banked(
    views: jax.Array,
    mask: jax.Array,
    self_vals: jax.Array,
    rules: Sequence[str],
    rule_idx,
    b,
    *,
    chunk: int | None = None,
) -> jax.Array:
    """`screen_views` with banked rule dispatch (see `screen_all_banked`)."""
    branches = [_rule_branch(r, chunk) for r in rules]
    if len(branches) == 1:
        return branches[0](views, mask, self_vals, b)
    return jax.lax.switch(rule_idx, branches, views, mask, self_vals, b)


# ---------------------------------------------------------------------------
# Banked dispatch with decisions (screening forensics — repro.obs)
# ---------------------------------------------------------------------------
#
# Same shape as the plain banked dispatch, but every branch runs the rule's
# decision-instrumented twin and returns ``(y [M, d], trim_frac [M, n])``.
# The decide path never streams coordinates (the trim matrix spans all of d by
# construction); callers must guard with `check_decide_streams` so engaging
# forensics where streaming would have engaged is a loud error, not a silent
# memory blowup.


def check_decide_streams(rules: Sequence[str], d: int, chunk: int | None) -> None:
    """Raise when screening forensics would collide with coordinate
    streaming (`_streams`): the decision path evaluates rules unchunked."""
    bad = [r for r in rules if _streams(r, d, chunk)]
    if bad:
        raise ValueError(
            f"screening forensics cannot stream coordinates: rules {bad} at d={d} "
            f"engage screen_chunk={chunk}; raise screen_chunk above d or set "
            f"TraceSpec(forensics=False)")


def _rule_branch_decide(rule: str, decide_stride: int, weighted: bool = False):
    fn = RULES_WITH_DECISIONS[rule]
    if weighted:
        # reputation-weighted dispatch (repro.trust): every branch of the
        # switch takes the [M, n] weight rows so signatures stay uniform;
        # rules outside WEIGHTED_RULES ignore the operand (eviction reaches
        # them through the mask)
        if rule in WEIGHTED_RULES:
            def run(values_per_node, mask_per_node, self_vals, b, weights):
                return jax.vmap(
                    lambda v, m, s, wt: fn(v, m, s, b, weights=wt,
                                           decide_stride=decide_stride))(
                    values_per_node, mask_per_node, self_vals, weights)
        else:
            def run(values_per_node, mask_per_node, self_vals, b, weights):
                del weights
                return jax.vmap(lambda v, m, s: fn(v, m, s, b,
                                                   decide_stride=decide_stride))(
                    values_per_node, mask_per_node, self_vals)
        return run

    def run(values_per_node, mask_per_node, self_vals, b):
        return jax.vmap(lambda v, m, s: fn(v, m, s, b, decide_stride=decide_stride))(
            values_per_node, mask_per_node, self_vals)

    return run


def _rule_branch_broadcast_decide(rule: str, decide_stride: int, weighted: bool = False):
    fn = RULES_WITH_DECISIONS[rule]
    if weighted:
        if rule in WEIGHTED_RULES:
            def run(w, adjacency, b, self_vals, weights):
                return jax.vmap(
                    lambda m, s, wt: fn(w, m, s, b, weights=wt,
                                        decide_stride=decide_stride))(
                    adjacency, self_vals, weights)
        else:
            def run(w, adjacency, b, self_vals, weights):
                del weights
                return jax.vmap(lambda m, s: fn(w, m, s, b,
                                                decide_stride=decide_stride))(
                    adjacency, self_vals)
        return run

    def run(w, adjacency, b, self_vals):
        return jax.vmap(lambda m, s: fn(w, m, s, b, decide_stride=decide_stride))(
            adjacency, self_vals)

    return run


def screen_all_decide_banked(
    w: jax.Array,
    adjacency: jax.Array,
    rules: Sequence[str],
    rule_idx,
    b,
    *,
    self_vals: jax.Array | None = None,
    decide_stride: int = 1,
    weights: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """`screen_all_banked` returning ``(y, trim_frac)`` — ``y`` bitwise-equal
    to the plain path, ``trim_frac[j, i]`` the fraction of coordinates on
    which receiver j excluded sender i this tick (estimated on every
    ``decide_stride``-th coordinate when > 1).  ``weights`` (``[M, n]``
    reputation rows, `repro.trust`) routes to rules in `WEIGHTED_RULES`;
    ``None`` keeps the exact unweighted program shape."""
    if self_vals is None:
        self_vals = w
    if weights is not None:
        branches = [_rule_branch_broadcast_decide(r, decide_stride, weighted=True)
                    for r in rules]
        if len(branches) == 1:
            return branches[0](w, adjacency, b, self_vals, weights)
        return jax.lax.switch(rule_idx, branches, w, adjacency, b, self_vals, weights)
    branches = [_rule_branch_broadcast_decide(r, decide_stride) for r in rules]
    if len(branches) == 1:
        return branches[0](w, adjacency, b, self_vals)
    return jax.lax.switch(rule_idx, branches, w, adjacency, b, self_vals)


def screen_views_decide_banked(
    views: jax.Array,
    mask: jax.Array,
    self_vals: jax.Array,
    rules: Sequence[str],
    rule_idx,
    b,
    *,
    decide_stride: int = 1,
    weights: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """`screen_views_banked` returning ``(y, trim_frac)`` (see
    `screen_all_decide_banked`); ``weights`` as there."""
    if weights is not None:
        branches = [_rule_branch_decide(r, decide_stride, weighted=True)
                    for r in rules]
        if len(branches) == 1:
            return branches[0](views, mask, self_vals, b, weights)
        return jax.lax.switch(rule_idx, branches, views, mask, self_vals, b, weights)
    branches = [_rule_branch_decide(r, decide_stride) for r in rules]
    if len(branches) == 1:
        return branches[0](views, mask, self_vals, b)
    return jax.lax.switch(rule_idx, branches, views, mask, self_vals, b)


# ---------------------------------------------------------------------------
# static-analysis contracts (checked by `python -m repro.analysis`)
# ---------------------------------------------------------------------------

from repro.analysis.contracts import Contract  # noqa: E402  (dependency-light)

CONTRACTS: tuple[Contract, ...] = (
    Contract(
        "screening.fence.survives", "fence",
        "every `fence` site survives the optimized HLO as a trip-count-2 "
        "while loop (XLA unrolls trip-count-<=1 loops, which would re-fuse "
        "the producer and void the storage-precision rounding)",
        params=(("min_fences", 1),),
    ),
    Contract(
        "screening.metrics.gradnorm_unfused", "fence",
        "the metrics-on program keeps exactly one more fence than its "
        "metrics-off twin: the grad-norm reduction stays un-CSE'd from the "
        "loss reduction (metrics-on bit-inertness)",
        params=(("delta", 1),),
    ),
    Contract(
        "screening.stream.partition", "lint",
        "every rule in RULES sits in exactly one of STREAMABLE_RULES / "
        "STREAM_REJECTED_RULES",
        params=(("check", "stream_partition"),),
    ),
    Contract(
        "screening.registries.complete", "lint",
        "MIN_NEIGHBORS, its traceable twin, and RULES_WITH_DECISIONS cover "
        "exactly RULES's keys; WEIGHTED_RULES is a subset",
        params=(("check", "registry_completeness"),),
    ),
)
