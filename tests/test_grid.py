"""repro.sim: the batched experiment-grid engine.

The engine's contract (ISSUE 2 acceptance):
* (a) every grid cell is bit-identical to the sequential per-experiment
  trainer (`BridgeTrainer` / `AsyncBridgeTrainer`) — params AND metric
  traces — for both the grouped and the fully banked execution paths;
* (b) chunked and unchunked grids agree bit-for-bit;
* (c) the full grid compiles ONCE (trace-count assertion), and chunking
  compiles per group, never per cell;
plus spec validation, the result store round-trip, and the batched
(leading-experiment-axis) kernels.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BridgeConfig, BridgeTrainer, erdos_renyi, replicate
from repro.net import AsyncBridgeConfig, AsyncBridgeTrainer
from repro.net.scenarios import get_scenario
from repro.sim import Cell, ExperimentGrid, GridEngine, GridResult, collect, existing_tags
from repro.sim.engine import stack_batches

M, D, T = 12, 5, 25


def quad_grad_fn(params, batch):
    w, c = params["w"], batch
    loss = 0.5 * jnp.sum((w - c) ** 2)
    return loss, {"w": w - c}


@pytest.fixture(scope="module")
def topo():
    return erdos_renyi(M, 0.8, 2, seed=1)


@pytest.fixture(scope="module")
def targets():
    rng = np.random.default_rng(0)
    return jnp.asarray(rng.normal(size=(M, D)), jnp.float32)


def init_fn(seed):
    return replicate({"w": jnp.zeros(D)}, M, perturb=0.1, key=jax.random.PRNGKey(seed))


@pytest.fixture(scope="module")
def batches(targets):
    return stack_batches(lambda i: targets, T)


def _sequential_sync(topo, targets, cell):
    # the cell's mask_seed (seed-axis-varying since ISSUE 4) maps onto the
    # trainer's byzantine_seed — same draw, same attacking nodes
    cfg = BridgeConfig(topology=topo, rule=cell.rule, num_byzantine=cell.b,
                       attack=cell.attack, lam=1.0, t0=10.0,
                       byzantine_seed=cell.mask_seed if cell.mask_seed is not None else 0)
    tr = BridgeTrainer(cfg, quad_grad_fn)
    st = tr.init(init_fn(cell.seed), seed=cell.seed)
    losses = []
    for _ in range(T):
        st, m = tr.step(st, targets)
        losses.append(m["loss"])
    return np.asarray(st.params["w"]), np.asarray(jnp.stack(losses))


# ---------------------------------------------------------------------------
# (a) per-cell bit-identity with the sequential trainers
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("group", [True, False])
def test_sync_grid_bit_equals_sequential_trainer(topo, targets, batches, group):
    """The acceptance grid — 2 rules x 3 attacks x 4 seeds — as one compiled
    program, every cell bit-for-bit equal to its own BridgeTrainer run."""
    grid = ExperimentGrid(topo, ("trimmed_mean", "median"),
                          ("random", "sign_flip", "alie"), (2,), (0, 1, 2, 3),
                          lam=1.0, t0=10.0)
    engine = GridEngine(grid, quad_grad_fn, group=group)
    state = engine.init(init_fn)
    final, metrics = engine.run(state, batches)
    assert engine.num_cells == 24
    for i, cell in enumerate(engine.cells):
        w_seq, loss_seq = _sequential_sync(topo, targets, cell)
        np.testing.assert_array_equal(w_seq, np.asarray(final.params["w"][i]),
                                      err_msg=f"params diverged for {cell}")
        np.testing.assert_array_equal(loss_seq, np.asarray(metrics["loss"][i]),
                                      err_msg=f"loss trace diverged for {cell}")


@pytest.mark.slow
def test_net_grid_bit_equals_async_trainer(topo, targets, batches):
    """Net-scenario cells (channel noise, churn, per-link attacks) are
    bit-identical to dedicated AsyncBridgeTrainer runs driven with the same
    schedules."""
    grid = ExperimentGrid(topo, ("trimmed_mean",), ("random", "selective_victim"),
                          (2,), (0, 1), scenarios=("ideal", "lossy_laggy", "churn"),
                          lam=1.0, t0=10.0)
    engine = GridEngine(grid, quad_grad_fn, num_ticks=T)
    state = engine.init(init_fn)
    final, metrics = engine.run(state, batches)
    for i, cell in enumerate(engine.cells):
        spec = get_scenario(cell.scenario)
        cfg = AsyncBridgeConfig(
            topology=topo, rule=cell.rule, num_byzantine=cell.b, attack=cell.attack,
            lam=1.0, t0=10.0, channel=spec.channel,
            staleness_bound=spec.staleness_bound,
            schedule=engine.runtime.schedule_for(cell.scenario),
            byzantine_seed=cell.mask_seed if cell.mask_seed is not None else 0,
        )
        tr = AsyncBridgeTrainer(cfg, quad_grad_fn)
        st = tr.init(init_fn(cell.seed), seed=cell.seed)
        st, ms = tr.run_scan(st, batches)
        np.testing.assert_array_equal(np.asarray(st.params["w"]),
                                      np.asarray(final.params["w"][i]),
                                      err_msg=f"params diverged for {cell}")
        np.testing.assert_array_equal(np.asarray(ms["loss"]),
                                      np.asarray(metrics["loss"][i]),
                                      err_msg=f"loss trace diverged for {cell}")


# ---------------------------------------------------------------------------
# (b) chunked == unchunked
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("chunk", [1, 3, 5, 24])
def test_chunked_matches_unchunked(topo, targets, batches, chunk):
    grid = ExperimentGrid(topo, ("trimmed_mean", "median"),
                          ("random", "sign_flip", "alie"), (2,), (0, 1, 2, 3),
                          lam=1.0, t0=10.0)
    engine = GridEngine(grid, quad_grad_fn)
    state = engine.init(init_fn)
    full, ms_full = engine.run(state, batches)
    part, ms_part = engine.run(state, batches, chunk=chunk)
    np.testing.assert_array_equal(np.asarray(full.params["w"]),
                                  np.asarray(part.params["w"]))
    for k in ms_full:
        np.testing.assert_array_equal(np.asarray(ms_full[k]), np.asarray(ms_part[k]),
                                      err_msg=f"metric {k} diverged under chunking")


# ---------------------------------------------------------------------------
# (c) compile-once
# ---------------------------------------------------------------------------


def test_full_grid_compiles_once(topo, targets, batches):
    grid = ExperimentGrid(topo, ("trimmed_mean", "median"),
                          ("random", "sign_flip", "alie"), (2,), (0, 1, 2, 3),
                          lam=1.0, t0=10.0)
    engine = GridEngine(grid, quad_grad_fn)
    state = engine.init(init_fn)
    assert engine.trace_count == 0
    engine.run(state, batches)
    assert engine.trace_count == 1  # 24 experiments, one compilation
    engine.run(state, batches)
    assert engine.trace_count == 1  # steady state: no retrace


def test_chunked_compiles_per_group_not_per_cell(topo, targets, batches):
    grid = ExperimentGrid(topo, ("trimmed_mean",), ("random",), (2,),
                          tuple(range(8)), lam=1.0, t0=10.0)
    engine = GridEngine(grid, quad_grad_fn)
    state = engine.init(init_fn)
    engine.run(state, batches, chunk=3)  # 3 chunks (3+3+2, tail padded)
    assert engine.trace_count == 1  # one group -> one compilation, not 3
    engine.run(state, batches, chunk=3)
    assert engine.trace_count == 1


# ---------------------------------------------------------------------------
# spec validation + result store
# ---------------------------------------------------------------------------


def test_grid_validation(topo):
    with pytest.raises(ValueError, match="network runtime"):
        ExperimentGrid(topo, ("trimmed_mean",), ("selective_victim",))  # sync grid
    with pytest.raises(ValueError, match="min in-degree"):
        ExperimentGrid(topo, ("bulyan",), ("random",), byzantine_counts=(4,))
    with pytest.raises(ValueError, match="duplicate"):
        ExperimentGrid(topo, ("trimmed_mean", "trimmed_mean"), ("random",))
    with pytest.raises(ValueError, match="unknown net scenario"):
        ExperimentGrid(topo, ("trimmed_mean",), ("random",), scenarios=("5g",))
    grid = ExperimentGrid(topo, ("trimmed_mean",), ("random",))
    with pytest.raises(ValueError, match="num_ticks"):
        GridEngine(ExperimentGrid(topo, ("trimmed_mean",), ("random",),
                                  scenarios=("lossy",)), quad_grad_fn)
    mixed = [Cell("trimmed_mean", "random", 1, 0, None),
             Cell("trimmed_mean", "random", 1, 0, "lossy")]
    with pytest.raises(ValueError, match="mix"):
        GridEngine(grid, quad_grad_fn, cells=mixed)


def test_grid_result_store_roundtrip(tmp_path, topo, targets, batches):
    grid = ExperimentGrid(topo, ("trimmed_mean",), ("random",), (2,), (0, 1),
                          lam=1.0, t0=10.0)
    engine = GridEngine(grid, quad_grad_fn)
    state = engine.init(init_fn)
    _, metrics = engine.run(state, batches)
    result = collect(engine.cells, metrics, meta={"ticks": T})
    assert len(result.cells) == 2
    assert all(np.isfinite(rec["final_loss"]) for rec in result.cells)
    path = tmp_path / "GridResult.json"
    result.save(str(path))
    loaded = GridResult.load(str(path))
    assert loaded.cells == result.cells and loaded.meta["ticks"] == T
    # per-cell store: resumability skips exactly the computed cells
    store = tmp_path / "cells"
    result.save_cells(str(store))
    tags = existing_tags(str(store))
    assert tags == {c.tag for c in engine.cells}
    pending = [c for c in grid.cells() if c.tag not in tags]
    assert pending == []
    assert len(result.rows(prefix="g")) == 2
    assert result.rows()[0][0].startswith("grid/")


# ---------------------------------------------------------------------------
# kernels: leading experiment axis
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("e,n,d,b", [(3, 9, 130, 1), (5, 12, 257, 2)])
def test_batched_kernels_match_per_experiment(e, n, d, b):
    from repro.kernels import ops, ref

    rng = np.random.default_rng(e * d)
    v = jnp.asarray(rng.normal(size=(e, n, d)), jnp.float32)
    mask = jnp.asarray(rng.random((e, n)) < 0.8).at[:, : 2 * b + 1].set(True)
    sv = jnp.asarray(rng.normal(size=(e, d)), jnp.float32)
    out = ops.trimmed_mean(v, mask, sv, b, block_d=128, interpret=True)
    assert out.shape == (e, d)
    exp = ref.trimmed_mean_ref(v, mask, sv, b)  # vmapped oracle
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=1e-5, atol=1e-5)
    for i in range(e):  # and the batch axis changes nothing per slice
        one = ops.trimmed_mean(v[i], mask[i], sv[i], b, block_d=128, interpret=True)
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(one),
                                   rtol=1e-6, atol=1e-6)
    om = ops.median(v, mask, block_d=128, interpret=True)
    em = ref.median_ref(v, mask)
    assert om.shape == (e, d)
    np.testing.assert_allclose(np.asarray(om), np.asarray(em), rtol=1e-5, atol=1e-5)
