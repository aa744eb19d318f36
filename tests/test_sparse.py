"""Sparse neighbor-indexed state (ISSUE 5).

Contracts pinned here:

* **dense<->sparse bit-identity** — the neighbor-indexed ``[M, K]`` layout
  (`repro.core.neighbors`) reproduces the dense oracle bit-for-bit: at the
  screening level for every registered rule, and end-to-end (params AND loss
  traces) for rule x attack x codec grids on both the synchronous and the
  unreliable-network paths — the full registered product in the ``slow``
  tier, a representative subset in the default tier;
* **padded-row inertness** — widening the table beyond the max in-degree
  changes no output bit, and padded mailbox slots never leave `NEVER`;
* **NEVER-sentinel behavior at large tick counts** — `staleness` saturates
  instead of overflowing ``tick - NEVER``, `usable_mask` never resurrects an
  empty slot;
* **starved-tick degree clamp** (satellite bugfix) — `effective_trim` keeps
  the trimmed mean finite when a churn/partition tick drops the usable
  in-degree below Table II's ``2b + 1`` (the static `validate_for_rule`
  cannot see dynamic schedules), and stays bit-identical at or above it;
* the fused Pallas gather->screen kernels agree exactly with the staged
  jnp path, and the sparse jitted step's HLO contains no ``[M, M, d]``-scale
  tensor (`repro.launch.hlo_analysis.largest_tensor_bytes`).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BridgeConfig, BridgeTrainer, erdos_renyi, replicate, screening
from repro.core.graph import random_geometric, small_world, toroidal_grid
from repro.core.neighbors import NeighborTable
from repro.net import AsyncBridgeConfig, AsyncBridgeTrainer, ChannelConfig
from repro.net import mailbox as mb
from repro.net.dynamic import edge_churn
from repro.sim import ExperimentGrid, GridEngine
from repro.sim.engine import stack_batches

M, D, T = 10, 6, 5


def quad_grad_fn(params, batch):
    w, c = params["w"], batch
    loss = 0.5 * jnp.sum((w - c) ** 2)
    return loss, {"w": w - c}


def init_fn(seed):
    return replicate({"w": jnp.zeros(D)}, M, perturb=0.1, key=jax.random.PRNGKey(seed))


@pytest.fixture(scope="module")
def topo():
    # dense enough for bulyan at b=1 (min degree 6) while degrees still vary
    for seed in range(1, 50):
        t = erdos_renyi(M, 0.8, 1, seed=seed)
        if t.min_in_degree >= 6 and len(set(t.in_degrees.tolist())) > 1:
            return t
    raise RuntimeError("no suitable fixture topology")


@pytest.fixture(scope="module")
def targets():
    rng = np.random.default_rng(0)
    return jnp.asarray(rng.normal(size=(M, D)), jnp.float32)


@pytest.fixture(scope="module")
def batches(targets):
    return stack_batches(lambda i: targets, T)


def _leaf_equal(x, y) -> bool:
    x, y = np.asarray(x), np.asarray(y)
    if x.dtype.kind == "f":
        # NaN == NaN positionally: the mean x garbage_codeword oracle cell
        # legitimately diverges to NaN (no screening, inf payloads) on BOTH
        # layouts, and jnp's == would call identical NaN trajectories unequal
        return bool(np.array_equal(x, y, equal_nan=True))
    return bool(np.array_equal(x, y))


def tree_bitwise_equal(a, b):
    return bool(jax.tree_util.tree_all(
        jax.tree_util.tree_map(_leaf_equal, a, b)))


# ---------------------------------------------------------------------------
# NeighborTable
# ---------------------------------------------------------------------------


def test_table_construction_and_gathers(topo):
    nbr = NeighborTable.from_adjacency(topo.adjacency)
    assert nbr.k == topo.in_degrees.max()
    for j in range(M):
        real = nbr.idx[j][nbr.valid[j]]
        np.testing.assert_array_equal(np.sort(real), np.nonzero(topo.adjacency[j])[0])
        assert (nbr.idx[j][~nbr.valid[j]] == M).all()  # sentinel index
    w = jnp.arange(M * D, dtype=jnp.float32).reshape(M, D)
    g = nbr.gather_rows(w)
    for j in range(M):
        for k in range(nbr.k):
            if nbr.valid[j, k]:
                assert bool(jnp.all(g[j, k] == w[nbr.idx[j, k]]))
    # schedule-union table covers churned edges
    sched = edge_churn(topo, 8, 0.4, seed=0)
    nbr_s = NeighborTable.from_schedule(sched)
    union = np.asarray(sched).any(axis=0)
    live = nbr_s.live_schedule(sched)
    assert live.shape == (8, M, nbr_s.k)
    assert live.sum() == np.asarray(sched).sum()
    assert nbr_s.valid.sum() == union.sum()


def test_sparse_flag_rejects_dense_runtime(topo):
    from repro.net.runtime import UnreliableRuntime

    cfg = BridgeConfig(topology=topo, rule="trimmed_mean", sparse=True)
    with pytest.raises(ValueError, match="dense runtime"):
        BridgeTrainer(cfg, quad_grad_fn, runtime=UnreliableRuntime(topo))


def test_edge_id_grid_matches_table(topo):
    from repro.core.neighbors import edge_id_grid

    nbr = NeighborTable.from_adjacency(topo.adjacency)
    grid_ids = edge_id_grid(M)
    for j in range(M):
        for k in range(nbr.k):
            if nbr.valid[j, k]:
                assert int(nbr.edge_ids[j, k]) == int(grid_ids[j, nbr.idx[j, k]])


def test_table_rejects_undersized_k(topo):
    kmax = int(topo.in_degrees.max())
    with pytest.raises(ValueError):
        NeighborTable.from_adjacency(topo.adjacency, k=kmax - 1)


# ---------------------------------------------------------------------------
# screening-level bit-identity + padded inertness
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rule", sorted(screening.RULES))
def test_screen_dense_sparse_bitwise(topo, rule):
    rng = np.random.default_rng(3)
    w = jnp.asarray(rng.normal(size=(M, D)).astype(np.float32) * 40)
    b = 1
    nbr = NeighborTable.from_adjacency(topo.adjacency)
    wide = NeighborTable.from_adjacency(topo.adjacency, k=nbr.k + 3)
    adj = jnp.asarray(topo.adjacency)
    dense = screening.screen_all_banked(w, adj, (rule,), 0, b)
    sparse = screening.screen_views_banked(
        nbr.gather_rows(w), nbr.valid_dev, w, (rule,), 0, b)
    padded = screening.screen_views_banked(
        wide.gather_rows(w), wide.valid_dev, w, (rule,), 0, b)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(sparse),
                                  err_msg=f"dense vs sparse diverged for {rule}")
    np.testing.assert_array_equal(np.asarray(sparse), np.asarray(padded),
                                  err_msg=f"padded rows not inert for {rule}")


# ---------------------------------------------------------------------------
# end-to-end bit-identity: rule x attack x codec grids, dense vs sparse
# ---------------------------------------------------------------------------

ALL_RULES = tuple(sorted(screening.RULES))
ALL_ATTACKS = ("none", "random", "sign_flip", "same_value", "alie", "shift",
               "selective_victim", "garbage_codeword", "scale_abuse", "index_lie")
ALL_CODECS = ("identity", "int8", "int4", "topk25", "randk25", "topk25_int8")


def _run_grid(topo, batches, *, rules, attacks, codecs, sparse, scenarios=("lossy_laggy", "churn")):
    grid = ExperimentGrid(topo, rules, attacks, (1,), (0,), scenarios=scenarios,
                          codecs=codecs, lam=1.0, t0=10.0)
    engine = GridEngine(grid, quad_grad_fn, num_ticks=T if scenarios else None,
                        sparse=sparse)
    state = engine.init(init_fn)
    final, metrics = engine.run(state, batches)
    return final.params, metrics["loss"]


def _assert_grid_pair(topo, batches, **kw):
    p_dense, l_dense = _run_grid(topo, batches, sparse=False, **kw)
    p_sparse, l_sparse = _run_grid(topo, batches, sparse=True, **kw)
    assert tree_bitwise_equal(p_dense, p_sparse), f"params diverged for {kw}"
    np.testing.assert_array_equal(np.asarray(l_dense), np.asarray(l_sparse),
                                  err_msg=f"loss traces diverged for {kw}")


def test_grid_dense_sparse_bit_identity_smoke(topo, batches):
    """Default-tier subset: representative rules/attacks/codecs on the net
    path (mailboxes, churn, channel noise) AND the sync path."""
    _assert_grid_pair(topo, batches, rules=("trimmed_mean", "median"),
                      attacks=("random", "selective_victim"), codecs=("identity",))
    _assert_grid_pair(topo, batches, rules=("trimmed_mean",),
                      attacks=("alie", "garbage_codeword"), codecs=("int8",))
    _assert_grid_pair(topo, batches, rules=("trimmed_mean", "krum"),
                      attacks=("random",), codecs=("identity",), scenarios=None)


@pytest.mark.slow
def test_grid_dense_sparse_bit_identity_all_rules_attacks(topo, batches):
    """Every registered rule x every attack tier (identity codec), one
    grouped grid per layout — the full-product acceptance half 1."""
    _assert_grid_pair(topo, batches, rules=ALL_RULES, attacks=ALL_ATTACKS,
                      codecs=("identity",))


@pytest.mark.slow
def test_grid_dense_sparse_bit_identity_all_codecs(topo, batches):
    """Every registered codec family x iterate/wire attacks (trimmed mean +
    median) — the full-product acceptance half 2."""
    _assert_grid_pair(topo, batches, rules=("trimmed_mean", "median"),
                      attacks=("alie", "garbage_codeword", "scale_abuse", "index_lie"),
                      codecs=ALL_CODECS)


@pytest.mark.slow
def test_sync_grid_dense_sparse_bit_identity_all(topo, batches):
    """The synchronous-broadcast path over every rule x broadcast attack."""
    _assert_grid_pair(topo, batches, rules=ALL_RULES,
                      attacks=("none", "random", "sign_flip", "alie", "shift"),
                      codecs=("identity", "int8"), scenarios=None)


def test_trainer_dense_sparse_bit_identity_lossy_channel(topo, targets):
    """AsyncBridgeTrainer twins: drop + latency + churn + int8 codec."""
    sched = edge_churn(topo, 2 * T, 0.2, seed=3)
    outs = []
    for sparse in (False, True):
        cfg = AsyncBridgeConfig(
            topology=topo, rule="trimmed_mean", num_byzantine=1, attack="alie",
            codec="int8", channel=ChannelConfig(drop_prob=0.15, latency_max=2),
            staleness_bound=3, schedule=sched, lam=1.0, t0=10.0, sparse=sparse)
        tr = AsyncBridgeTrainer(cfg, quad_grad_fn)
        st, ms = tr.run_ticks(tr.init(init_fn(0), seed=0), lambda i: targets, 2 * T)
        outs.append((st.params, ms["loss"], ms["delivered_frac"], ms["usable_in"]))
    assert tree_bitwise_equal(outs[0][0], outs[1][0])
    for a, b in zip(outs[0][1:], outs[1][1:], strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_adversary_sparse_runtime_close(topo, targets):
    """Adaptive adversaries run on the sparse runtime; the inner-max ascent
    differentiates through a gather instead of a mask-select, so this pins
    allclose (bitwise holds for the attack/codec tiers above)."""
    outs = []
    for sparse in (False, True):
        cfg = AsyncBridgeConfig(topology=topo, rule="trimmed_mean", num_byzantine=1,
                                adversary="dissensus", lam=1.0, t0=10.0, sparse=sparse)
        tr = AsyncBridgeTrainer(cfg, quad_grad_fn)
        st, ms = tr.run_ticks(tr.init(init_fn(0), seed=0), lambda i: targets, T)
        outs.append(np.asarray(st.params["w"]))
    np.testing.assert_allclose(outs[0], outs[1], rtol=2e-5, atol=2e-6)


def test_padded_width_inert_end_to_end(topo, targets):
    """A runtime whose table is padded wider than the max in-degree is
    bit-identical to the tight one (padded slots never change any output),
    and its padded mailbox slots stay at NEVER forever."""
    from repro.net.runtime import SparseUnreliableRuntime

    sched = edge_churn(topo, T, 0.2, seed=5)
    outs, states = [], []
    for extra_k in (0, 4):
        nbr = NeighborTable.from_schedule(sched,
                                          k=NeighborTable.from_schedule(sched).k + extra_k)
        runtime = SparseUnreliableRuntime(sched, ChannelConfig(drop_prob=0.1),
                                          staleness_bound=3, neighbors=nbr)
        cfg = BridgeConfig(topology=topo, rule="trimmed_mean", num_byzantine=1,
                           attack="random", lam=1.0, t0=10.0)
        tr = BridgeTrainer(cfg, quad_grad_fn, runtime=runtime)
        st = tr.init(init_fn(0), seed=0)
        for i in range(T):
            st, _ = tr.step(st, targets)
        outs.append(st.params)
        states.append((nbr, st.net))
    assert tree_bitwise_equal(outs[0], outs[1])
    nbr, net = states[1]
    pad = ~jnp.asarray(nbr.valid)
    assert bool(jnp.all(jnp.where(pad, net.send_tick, mb.NEVER) == mb.NEVER))
    assert bool(jnp.all(jnp.where(pad[..., None], net.ring_valid, False) == False))  # noqa: E712


# ---------------------------------------------------------------------------
# NEVER sentinel at large tick counts
# ---------------------------------------------------------------------------


def test_staleness_saturates_and_usable_mask_no_overflow():
    state = mb.init_mailbox(2, 3, max_delay=1, width=2)
    # one real delivery at tick 0 on slot (0, 0)
    msgs = jnp.ones((2, 2, 3))
    send = jnp.zeros((2, 2), bool).at[0, 0].set(True)
    state = mb.push(state, msgs, send, jnp.zeros((2, 2), jnp.int32), jnp.int32(0))
    state, arrived = mb.deliver(state, jnp.int32(0))
    assert bool(arrived[0, 0])
    for t in (5, 2**30, 2**31 - 2):  # far past the int32 overflow of t - NEVER
        tt = jnp.int32(t)
        stale = mb.staleness(state, tt)
        usable = mb.usable_mask(state, tt, bound=10)
        # empty slots: saturated staleness, never usable
        assert int(stale[1, 1]) == np.iinfo(np.int32).max
        assert not bool(usable[1, 1])
        # the real entry: exact staleness, usable iff within bound
        assert int(stale[0, 0]) == t
        assert bool(usable[0, 0]) == (t <= 10)


# ---------------------------------------------------------------------------
# starved-tick trim clamp (satellite bugfix)
# ---------------------------------------------------------------------------


def test_effective_trim_clamp():
    b = jnp.int32(2)
    assert int(screening.effective_trim(b, 5)) == 2  # at the 2b+1 bound
    assert int(screening.effective_trim(b, 7)) == 2  # above: identity
    assert int(screening.effective_trim(b, 4)) == 1  # starved: clamp
    assert int(screening.effective_trim(b, 1)) == 0
    assert int(screening.effective_trim(b, 0)) == 0


def test_trimmed_mean_starved_tick_stays_finite():
    """In-degree 1 with b=1 used to divide by count - 2b + 1 == 0 and sweep
    +inf sentinels into the window; the clamp degrades to an untrimmed mean
    over what's live instead."""
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(4, D)), jnp.float32)
    starved = jnp.zeros((4, 4), bool).at[0, 1].set(True).at[1, 0].set(True)
    starved = starved.at[2, 3].set(True).at[3, 2].set(True)
    y = screening.screen_all_banked(w, starved, ("trimmed_mean",), 0, 1)
    assert bool(jnp.all(jnp.isfinite(y)))
    # count=1, b_eff=0: mean of the single neighbor and self
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray((w[1] + w[0]) / 2.0),
                               rtol=1e-6)
    # and at/above the Table-II bound the clamp is the identity (bitwise)
    full = jnp.asarray(~np.eye(4, dtype=bool))
    y_full = screening.screen_all_banked(w, full, ("trimmed_mean",), 0, 1)
    order = jnp.sort(jnp.where(full[0][:, None], w, jnp.inf), axis=0)
    ref0 = (order[1] + w[0]) / 2.0  # 3 neighbors, trim 1 high 1 low, + self
    np.testing.assert_allclose(np.asarray(y_full[0]), np.asarray(ref0), rtol=1e-6)


def test_churn_below_min_degree_regression(topo, targets):
    """A churn schedule that dips live in-degree below 2b+1: training stays
    finite, and on starved ticks a node's update freezes to its own iterate
    (pure local SGD) — the runtime guard + clamp acting together."""
    sched = np.asarray(edge_churn(topo, 4 * T, 0.85, seed=9))  # heavy churn
    in_deg = sched.sum(axis=2)
    assert in_deg.min() < 3, "fixture must actually dip below 2b+1"
    for sparse in (False, True):
        cfg = AsyncBridgeConfig(topology=topo, rule="trimmed_mean", num_byzantine=1,
                                attack="random", schedule=sched, staleness_bound=0,
                                lam=1.0, t0=10.0, sparse=sparse)
        tr = AsyncBridgeTrainer(cfg, quad_grad_fn)
        st, ms = tr.run_ticks(tr.init(init_fn(0), seed=0), lambda i: targets, 4 * T)
        assert bool(jnp.all(jnp.isfinite(st.params["w"]))), "params blew up under churn"
        assert np.isfinite(np.asarray(ms["loss"])).all()
        assert float(np.asarray(ms["screened_frac"]).min()) < 1.0  # freeze engaged


# ---------------------------------------------------------------------------
# fused Pallas gather->screen kernels + HLO layout assertion
# ---------------------------------------------------------------------------


def test_gather_screen_kernels_match_staged(topo):
    from repro.comm.codec import SCALE_BLOCK
    from repro.kernels.gather_screen import (
        gather_dequant_screen_pallas,
        gather_screen_pallas,
    )

    rng = np.random.default_rng(2)
    d = 300
    w = jnp.asarray(rng.normal(size=(M, d)).astype(np.float32) * 30)
    nbr = NeighborTable.from_adjacency(topo.adjacency)
    idx, valid = jnp.asarray(nbr.idx), nbr.valid_dev
    for rule in ("trimmed_mean", "median"):
        ref = screening.screen_views_banked(nbr.gather_rows(w), valid, w, (rule,), 0, 1)
        out = gather_screen_pallas(w, idx, valid, w, 1, rule=rule, block_d=128, interpret=True)
        # kernel blocks extract extrema iteratively (VPU-friendly) while the
        # jnp rule sorts — same survivors, different summation order, so the
        # comparison is allclose (the test_kernels convention)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   rtol=1e-5, atol=1e-5)
    # int8 codeword variant vs gather + the existing dequant_screen kernels
    from repro.kernels.dequant_screen import dequant_trimmed_mean_pallas

    q = jnp.asarray(rng.integers(-128, 128, size=(M, d)).astype(np.int8))
    s = -(-d // SCALE_BLOCK)
    scale = jnp.asarray(np.stack([rng.uniform(0.01, 0.1, size=(M, s)),
                                  rng.uniform(-1, 1, size=(M, s))], -1), jnp.float32)
    staged = dequant_trimmed_mean_pallas(
        jnp.take(q, nbr.safe_idx, axis=0), jnp.take(scale, nbr.safe_idx, axis=0),
        valid, w, 1, block_d=128, interpret=True)
    fused = gather_dequant_screen_pallas(q, scale, idx, valid, w, 1,
                                         rule="trimmed_mean", block_d=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(staged), np.asarray(fused))


@pytest.mark.slow
def test_sparse_step_hlo_has_no_dense_tensor():
    """The jitted sparse runtime step never materializes an [M, M, d]-scale
    tensor (scale_bench asserts the same at M = 512)."""
    from repro.launch import hlo_analysis

    m, d = 64, 256
    topo64 = small_world(m, 5, 1, seed=0)
    rng = np.random.default_rng(0)
    targets = jnp.asarray(rng.normal(size=(m, d)), jnp.float32)

    def gfn(params, batch):
        w = params["w"]
        return 0.5 * jnp.sum((w - batch) ** 2), {"w": w - batch}

    cfg = AsyncBridgeConfig(topology=topo64, rule="trimmed_mean", num_byzantine=1,
                            attack="alie", channel=ChannelConfig(drop_prob=0.1),
                            lam=1.0, t0=10.0, sparse=True)
    tr = AsyncBridgeTrainer(cfg, gfn)
    st = tr.init(replicate({"w": jnp.zeros(d)}, m, perturb=0.1,
                           key=jax.random.PRNGKey(0)), seed=0)
    text = jax.jit(tr._raw_step).lower(tr._cell, st, targets).compile().as_text()
    largest = hlo_analysis.largest_tensor_bytes(text)
    assert largest < m * m * d * 4, f"dense-scale tensor in sparse HLO: {largest}"


# ---------------------------------------------------------------------------
# large-graph topology builders
# ---------------------------------------------------------------------------


def test_large_topology_builders():
    sw = small_world(64, 4, 1, seed=0)
    assert sw.min_in_degree >= 3 and sw.in_degrees.max() <= 16
    # rewiring must never starve a node below the Table-II floor: at
    # nearest=3, b=2 the lattice degree (6) is exactly sufficient and every
    # rewire decrement is at risk of crossing 2b+1=5 (regression: the floor
    # check used to look at the lattice only)
    for seed in range(4):
        assert small_world(256, 3, 2, seed=seed).min_in_degree >= 5
    assert not np.asarray(sw.adjacency).diagonal().any()
    assert (sw.adjacency == sw.adjacency.T).all()
    geo = random_geometric(64, 1, seed=0)
    assert geo.min_in_degree >= 3
    tor = toroidal_grid(8, 8, 1)
    assert (tor.in_degrees == 4).all()
    tor8 = toroidal_grid(8, 8, 1, diagonal=True)
    assert (tor8.in_degrees == 8).all()
    from repro.core.graph import make_topology

    assert make_topology("small_world:4", 64, 1).num_nodes == 64
    assert make_topology("torus:8", 64, 1).num_nodes == 64
    with pytest.raises(ValueError):
        make_topology("nope", 8, 0)


def test_erdos_renyi_check_plumbing(monkeypatch):
    """check_samples reaches check_assumption4 (it was hardcoded to 25), and
    large M takes the degree-only fast path (no sampler call at all)."""
    import repro.core.graph as graph_lib

    calls = {}
    real = graph_lib.check_assumption4

    def spy(topo, *, num_samples=50, seed=0, byzantine_sets=None):
        calls["num_samples"] = num_samples
        return real(topo, num_samples=num_samples, seed=seed,
                    byzantine_sets=byzantine_sets)

    monkeypatch.setattr(graph_lib, "check_assumption4", spy)
    graph_lib.erdos_renyi(10, 0.8, 1, seed=0, check_samples=7)
    assert calls["num_samples"] == 7
    calls.clear()
    # degree-only fast path: the sampler must not run above DEGREE_ONLY_NODES
    topo = graph_lib.erdos_renyi(graph_lib.DEGREE_ONLY_NODES + 16, 0.3, 1, seed=0)
    assert calls == {}
    assert topo.min_in_degree > 2
    # explicit override forces sampling even at large M
    graph_lib.erdos_renyi(10, 0.8, 1, seed=0, assumption4="sampled")
    assert calls["num_samples"] == 50
