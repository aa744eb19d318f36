"""The BRIDGE trainer — Algorithm 1 of the paper.

Two execution paths share the same screening code:

* **Simulation path** (this module): all M node replicas live on one host as a
  stacked ``[M, ...]`` pytree; per-iteration we (1) apply the Byzantine attack
  to the *broadcast* matrix, (2) screen at every honest node, (3) take the
  local gradient step  w_j(t+1) = y_j(t) - rho(t) * grad f_j(w_j(t)).
  This is the path used by the paper-replication benchmarks (MNIST-scale).

* **Sharded path** (`repro.core.gossip` + `repro.launch`): the same protocol
  over a TPU mesh where the node axis is sharded over ("pod","data") and each
  replica is tensor-parallel over "model".
"""
from __future__ import annotations

import dataclasses
from collections.abc import Callable
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.adversary import protocols as adv_lib
from repro.comm import codec as codec_lib
from repro.comm import exchange as comm_lib
from repro.core import byzantine as byz_lib
from repro.core import screening
from repro.core import neighbors as neighbors_lib
from repro.core.graph import Topology
from repro.core.neighbors import NeighborTable


class BridgeState(NamedTuple):
    params: Any  # pytree with leading node axis [M, ...]
    t: jax.Array  # iteration counter
    key: jax.Array
    net: Any = None  # network-runtime state (mailboxes etc.); None when synchronous
    # error-feedback residual of the wire codec (repro.comm): [M, d] per
    # sender on the broadcast path, [M, M, d] per link on the runtime path;
    # None when every codec in the bank is lossless (the default identity
    # path carries no extra state)
    comm: Any = None
    # adversary tracking state (repro.adversary.AdvState): the omniscient
    # adversary's carried observations of the honest trajectory; None when no
    # adversary in the bank is stateful (static attacks carry nothing)
    adv: Any = None
    # observability aggregates (repro.obs.trace.TraceState): in-scan screening
    # forensics, histograms, and the divergence sentinel; None (the default)
    # keeps the untraced program shape bit-for-bit
    obs: Any = None
    # trust carry (repro.trust.reputation.TrustState): per-edge suspicion,
    # reputation weights, and latched evictions; None (the default) keeps the
    # trust-free program shape bit-for-bit
    trust: Any = None
    # live-metric ring (repro.obs.metrics.MetricState): the [C, S] per-tick
    # scalar streams the chunked runners flush to metrics.jsonl between
    # dispatches; None (the default) keeps the metric-free program shape
    # bit-for-bit
    mets: Any = None


class CellParams(NamedTuple):
    """One experiment cell's runtime-switchable parameters.

    `BridgeTrainer` binds a single constant cell from its config; the batched
    grid engine (`repro.sim`) stacks one row per experiment and ``vmap``s the
    shared step over the leading axis.  Rule/attack selection is *data* — an
    int32 index into a static bank resolved by ``lax.switch`` — so E
    experiments with different rules, attacks, Byzantine counts, and step-size
    schedules share one compiled program.
    """

    rule_idx: jax.Array  # int32 index into the step's static rule bank
    attack_idx: jax.Array  # int32 index into the step's static attack bank
    b: jax.Array  # int32 Byzantine bound fed to the screening rule
    byz_mask: jax.Array  # [M] bool — which nodes actually attack
    lam: jax.Array  # f32 step-size decay rate
    t0: jax.Array  # f32 step-size offset
    lr: jax.Array  # f32 constant step size; 0 -> decaying 1/(lam*(t0+t))
    # int32 index into a scenario-banked runtime's bank (grid net path);
    # None on the single-runtime trainer path (no scenario axis).
    scenario_idx: Any = None
    # int32 index into the step's static wire-codec bank (repro.comm);
    # None selects entry 0 (single-codec trainers).
    codec_idx: Any = None
    # int32 index into the step's static adversary bank (repro.adversary);
    # None selects entry 0 (single-adversary trainers / no adversary axis).
    adv_idx: Any = None
    # [THETA_DIM] f32 per-cell adversary hyperparameters (attack scale / z /
    # ascent steps — see repro.adversary.adaptive); None -> the selected
    # adversary's registered defaults.  Data, not structure: the red-team
    # search mutates these between generations without retracing.
    adv_theta: Any = None
    # observability spec (repro.obs.trace.TraceSpec): *structural* auxiliary
    # data — a zero-leaf pytree node, so it is part of the jit cache key, not
    # an operand.  None (the default) keeps the exact untraced program shape;
    # a spec compiles forensic aggregation into the step (bit-inert for the
    # trajectory — property-tested).
    trace: Any = None
    # trust spec (repro.trust.reputation.TrustSpec): structural like `trace`
    # — None keeps the exact trust-free program; a spec compiles reputation
    # updates, eviction masking, and (net path) the echo protocol into the
    # step.  Unlike `trace`, trust ON deliberately changes the trajectory.
    trust: Any = None
    # live-metric spec (repro.obs.metrics.MetricSpec): structural like
    # `trace` — None keeps the exact metric-free program; a spec compiles the
    # per-tick scalar ring into the step (bit-inert for the trajectory —
    # the ring only reads values the step already computes).
    metrics: Any = None


def cell_step_size(cell: CellParams, t: jax.Array) -> jax.Array:
    """rho(t) = lr if lr > 0 else 1 / (lam * (t0 + t))  (Sec. IV)."""
    decayed = 1.0 / (cell.lam * (cell.t0 + t))
    return jnp.where(cell.lr > 0, cell.lr, decayed)


@dataclasses.dataclass(frozen=True)
class BridgeConfig:
    """Everything one BRIDGE trainer needs: graph, screening rule, threat
    model, wire format, step-size schedule, and the optional observability /
    trust specs.  Frozen — a config is a value, and `BridgeTrainer` derives
    all jit structure from it once at construction.

    Minimal usage::

        from repro.core.bridge import BridgeConfig, BridgeTrainer, replicate
        from repro.core.graph import erdos_renyi

        topo = erdos_renyi(10, 0.8, 2, seed=1)
        cfg = BridgeConfig(topology=topo, rule="trimmed_mean",
                           num_byzantine=2, attack="sign_flip")
        trainer = BridgeTrainer(cfg, grad_fn)          # grad_fn(params, batch)
        state = trainer.init(replicate(params0, 10))
        state, metrics = trainer.step(state, batch)

    See docs/ARCHITECTURE.md for the full one-tick dataflow the trainer
    compiles (attack -> adversary -> codec -> exchange -> screen -> apply ->
    obs/trust).
    """

    topology: Topology
    rule: str = "trimmed_mean"  # trimmed_mean | median | krum | bulyan | mean
    num_byzantine: int = 0  # the bound b given to the screening rule
    attack: str = "none"
    # adaptive adversary (repro.adversary): none | ipm | alie_online |
    # dissensus | inner_max | any static attack name (stateless tier).
    # Composes after `attack` (both substitute Byzantine rows, so use one).
    adversary: str = "none"
    codec: str = "identity"  # wire codec (repro.comm): identity | int8 | int4 | topk<P>...
    byzantine_seed: int = 0
    # step size rho(t) = 1 / (lam * (t0 + t))  (Sec. IV); or constant if lr>0
    lam: float = 1.0
    t0: float = 50.0
    lr: float = 0.0  # if > 0, use constant step size instead
    screen_chunk: int | None = 1 << 20  # coordinate streaming chunk
    # neighbor-indexed [M, K] state layout (repro.core.neighbors): screening
    # consumes gathered [M, K, d] views instead of masking the full [M, d]
    # broadcast per node — bit-identical to the dense path (property-tested)
    # and the only layout that scales past the dense O(M^2) wall
    sparse: bool = False
    # observability (repro.obs.trace.TraceSpec); None = untraced (default)
    trace: Any = None
    # trust layer (repro.trust.reputation.TrustSpec); None = off (default,
    # bit-inert) — a spec turns on reputation-weighted screening + eviction
    # (pair it with a rule from screening.WEIGHTED_RULES for soft weighting;
    # any rule gets hard eviction through the mask)
    trust: Any = None
    # live metrics (repro.obs.metrics.MetricSpec); None = off (default,
    # bit-inert) — a spec compiles the per-tick scalar ring into the step
    # and `run_chunks` flushes it to metrics.jsonl between dispatches
    metrics: Any = None

    def step_size(self, t: jax.Array) -> jax.Array:
        if self.lr > 0:
            return jnp.asarray(self.lr, jnp.float32)
        return 1.0 / (self.lam * (self.t0 + t))


def stack_batches(batch_fn: Callable[[int], Any], num_ticks: int) -> Any:
    """Materialize ``num_ticks`` batches on a new leading axis — the ``xs``
    the scan-over-ticks paths consume.  The single definition shared by
    `AsyncBridgeTrainer.run_ticks`, the grid engine and `run_chunks`, so all
    scan identical inputs (part of their bit-identity contract)."""
    return stack_puts(put_batches([batch_fn(i) for i in range(num_ticks)]))


def put_batches(batches: list) -> list:
    """Every leaf of every batch on the device (``jnp.asarray``): the
    host-to-device half of `stack_batches`."""
    return [jax.tree_util.tree_map(jnp.asarray, b) for b in batches]


def stack_puts(puts: list) -> Any:
    """`put_batches`' per-tick batches stacked on a new leading axis: the
    device half of `stack_batches`."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *puts)


def host_nbytes(batches: list) -> int:
    """Bytes of the host (not yet device) leaves of ``batches``: what
    `put_batches` moves to the device."""
    return sum(np.asarray(x).nbytes for x in jax.tree_util.tree_leaves(batches)
               if not isinstance(x, jax.Array))


def stack_flatten(params: Any) -> tuple[jax.Array, Callable[[jax.Array], Any]]:
    """[M, ...] pytree -> ([M, D] f32 matrix, unflatten).

    Screening always runs in f32; ``unflatten`` restores each leaf's own
    storage dtype, so mixed bf16/f32 pytrees round-trip without a silent
    upcast (regression-pinned by ``tests/test_bridge.py``).  The per-leaf
    dtypes are captured as *static* values — not by closing over the input
    leaves — so the closure never pins the original arrays alive across a
    step.  Note the f32 flat copy itself is the cost this function cannot
    avoid; `repro.stream` exists so LLM-scale runs never call it.
    """
    leaves, treedef = jax.tree_util.tree_flatten(params)
    m = leaves[0].shape[0]
    shapes = [l.shape[1:] for l in leaves]
    sizes = [int(np.prod(s)) if s else 1 for s in shapes]
    dtypes = [jnp.dtype(l.dtype) for l in leaves]
    flat = jnp.concatenate([l.reshape(m, -1).astype(jnp.float32) for l in leaves], axis=1)

    def unflatten(w: jax.Array) -> Any:
        outs, off = [], 0
        for shape, size, dtype in zip(shapes, sizes, dtypes, strict=True):
            outs.append(w[:, off : off + size].reshape((m,) + shape).astype(dtype))
            off += size
        return jax.tree_util.tree_unflatten(treedef, outs)

    return flat, unflatten


# ---------------------------------------------------------------------------
# Cell-parameterized step builders
# ---------------------------------------------------------------------------
#
# One BRIDGE iteration, parameterized by a `CellParams` row plus static banks
# of rules/attacks.  `BridgeTrainer` binds a constant single-entry-bank cell
# (bit-identical to dedicated dispatch — the switches are elided); the grid
# engine vmaps the same function over stacked cells.  This is the single
# definition of Algorithm 1's iteration — the batched path reuses it rather
# than forking it.

# Salt decorrelating the channel PRNG stream from the attack stream (both
# derive from the same per-step subkey).
NET_SALT = 0x6E657430
# Salts for the wire-codec streams (stochastic rounding / codeword attacks),
# decorrelated from both the attack and the channel streams.
COMM_SALT = 0x636D6D30
WIRE_SALT = 0x77697230
# Salt for the adaptive-adversary stream (repro.adversary).
ADV_SALT = 0x61647630
# Salt for the trust layer's echo-digest stream (repro.trust.echo): the
# tick's public random projection derives from this fold, decorrelated from
# every other consumer of the step subkey.
TRUST_SALT = 0x74727530


def _cell_codec_idx(cell: CellParams):
    """codec bank index; None (single-codec trainers) selects entry 0."""
    if cell.codec_idx is None:
        return jnp.zeros((), jnp.int32)
    return cell.codec_idx


def _cell_adv_idx(cell: CellParams):
    """adversary bank index; None (single-adversary trainers) selects 0."""
    if cell.adv_idx is None:
        return jnp.zeros((), jnp.int32)
    return cell.adv_idx


def _wire_roundtrip(codec_bank, wire_bank, cell, sub, x, residual, byz, t, d, eids=None):
    """Encode -> codeword attack -> decode, with error feedback.

    Returns ``(x_hat, residual')`` — what receivers see and the advanced
    per-sender (or per-link) EF carry.  When nothing in the banks can alter a
    payload (all-lossless codecs, no wire attacks) the wire is skipped
    entirely: the default identity path stays structurally identical to the
    uncompressed trainer, which is the bit-identity contract the tests pin.
    ``eids`` (the per-link paths) re-keys every PRNG consumer — stochastic
    codec rounding, randk index draws, randomized wire attacks — per *edge
    id* instead of per tensor, so the dense ``[M, M, d]`` and sparse
    ``[M, K, d]`` layouts produce bitwise-identical codewords on matching
    edges (the dense<->sparse bit-identity contract for lossy codecs).
    """
    if comm_lib.bank_is_lossless(codec_bank) and all(a.name == "none" for a in wire_bank):
        return x, residual
    cidx = _cell_codec_idx(cell)
    comm_key = jax.random.fold_in(sub, COMM_SALT)
    wire_key = jax.random.fold_in(sub, WIRE_SALT)
    if eids is None:
        msg, target = comm_lib.encode_bank(codec_bank, cidx, comm_key, x, residual)
        msg = byz_lib.apply_wire_attack_bank(wire_bank, cell.attack_idx, msg, byz, wire_key, t, d)
        return comm_lib.decode_bank(codec_bank, cidx, msg, target, residual, comm_key)

    lead = x.shape[:-1]  # [M, M] or [M, K]

    def per_edge(eid, x_e, byz_e, st_e):
        ck = jax.random.fold_in(comm_key, eid)
        wk = jax.random.fold_in(wire_key, eid)
        msg, target = comm_lib.encode_bank(codec_bank, cidx, ck, x_e, st_e)
        msg = byz_lib.apply_wire_attack_bank(wire_bank, cell.attack_idx, msg, byz_e, wk, t, d)
        return comm_lib.decode_bank(codec_bank, cidx, msg, target, st_e, ck)

    flat = lambda a: a.reshape((-1,) + a.shape[len(lead):])
    st_flat = None if residual is None else jax.tree_util.tree_map(flat, residual)
    x_hat, st = jax.vmap(per_edge)(flat(eids), flat(x), flat(byz), st_flat)
    unflat = lambda a: a.reshape(lead + a.shape[1:])
    return unflat(x_hat), (None if residual is None else jax.tree_util.tree_map(unflat, st))


def _comm_metrics(codec_bank, cell, d: int, live_edges, residual) -> dict:
    """Exact bits-on-wire accounting + EF diagnostics (uniform keys across
    codec banks so grid groups concatenate)."""
    bits = comm_lib.wire_bits_bank(codec_bank, _cell_codec_idx(cell), d)
    bits_f = jnp.asarray(bits, jnp.float32)
    res = (jnp.zeros((), jnp.float32) if residual is None
           else jnp.sqrt(jnp.sum(residual.resid * residual.resid)))
    return {
        "wire_bits_per_edge": bits_f,
        "wire_bytes_total": bits_f / 8.0 * live_edges,
        "ef_residual_norm": res,
    }


def _grad_update_and_metrics(grad_fn, cell: CellParams, state: BridgeState, batch, y, unflatten):
    """(Step 6) local gradient update at w_j(t) + shared diagnostics.

    ``rho * g`` passes through `screening.fence` before the subtract: whether
    XLA contracts ``y - rho * g`` into an FNMA is *program-shape dependent*,
    and the grid's banked program and the trainer's single-bank program would
    otherwise drift ~1 ULP/step apart — breaking the bit-for-bit
    grid<->trainer contract the tests pin."""
    losses, grads = jax.vmap(grad_fn)(state.params, batch)
    g, _ = stack_flatten(grads)
    rho = cell_step_size(cell, state.t)
    w_new = y - screening.fence(rho * g)
    new_params = unflatten(w_new)
    # consensus diagnostic over honest nodes
    hm = ~cell.byz_mask
    cnt = jnp.sum(hm)
    mu = jnp.sum(jnp.where(hm[:, None], w_new, 0.0), axis=0) / cnt
    dev = jnp.where(hm[:, None], w_new - mu[None, :], 0.0)
    cons = jnp.sqrt(jnp.max(jnp.sum(dev * dev, axis=1)))
    metrics = {
        "loss": jnp.sum(jnp.where(hm, losses, 0.0)) / cnt,
        "consensus_dist": cons,
        "rho": rho,
    }
    if cell.metrics is not None:
        # honest-mean per-node gradient norm — the live-metric ring's
        # grad_norm column; gated on the (static) spec so the metric-free
        # program shape is untouched.  The fence severs CSE with the loss
        # reduction (grad_fn often shares g*g subexpressions with its loss),
        # which would otherwise re-fuse and ULP-shift the loss stream —
        # breaking metrics-on bit-inertness
        gf = screening.fence(g)
        gn = jnp.sqrt(jnp.sum(gf * gf, axis=1))
        metrics["grad_norm"] = jnp.sum(jnp.where(hm, gn, 0.0)) / cnt
    return new_params, metrics


def _fold_metric_ring(mspec, state: BridgeState, metrics: dict, *,
                      staleness=None, live=None):
    """Fold the tick's already-computed scalars into the live-metric ring
    (`repro.obs.metrics`).  Reads only — bit-inert for the trajectory; the
    whole call is gated on the (static) spec so ``metrics=None`` keeps the
    exact pre-metrics program."""
    if mspec is None:
        return state.mets
    from repro.obs import metrics as obs_metrics

    with jax.named_scope("bridge.metrics"):
        vals = {k: metrics[k]
                for k in ("loss", "consensus_dist", "grad_norm", "rho",
                          "wire_bits_per_edge", "wire_bytes_total")
                if k in metrics}
        if "obs_trim_frac" in metrics:
            vals["trim_frac"] = metrics["obs_trim_frac"]
        if "trust_evicted_frac" in metrics:
            vals["evicted_frac"] = metrics["trust_evicted_frac"]
        if staleness is not None and live is not None:
            vals.update(obs_metrics.stale_quantiles(staleness, live))
        return obs_metrics.update(mspec, state.mets, t=state.t, vals=vals)


def build_cell_step(grad_fn, adjacency, rules: tuple[str, ...], attacks, *,
                    codecs: tuple[str, ...] = ("identity",), wire_attacks=None,
                    adversaries: tuple[str, ...] | None = None,
                    screen_chunk=None, neighbors: NeighborTable | None = None):
    """The synchronous-broadcast iteration: ``step(cell, state, batch)``.

    ``rules`` is a static bank of screening-rule names, ``attacks`` a static
    bank of `byzantine.Attack`s, ``codecs`` a static bank of wire-codec names
    (`repro.comm`), ``wire_attacks`` the codeword-domain bank parallel to
    ``attacks`` (defaults to all no-ops), and ``adversaries`` a static bank
    of `repro.adversary` names (None / all-`none` skips the adversary stage
    structurally — the default path stays bit-identical); ``cell`` selects
    into all of them.

    ``neighbors`` switches screening to the neighbor-indexed sparse layout
    (`repro.core.neighbors`): each node screens its gathered ``[K, d]`` view
    instead of masking the full ``[M, d]`` broadcast — bit-identical outputs
    (property-tested), ``O(M K d)`` instead of ``O(M^2 d)`` work.
    """
    codec_bank = codec_lib.codec_bank(codecs)
    if wire_attacks is None:
        wire_attacks = (byz_lib.WIRE_ATTACKS["none"],) * len(attacks)
    adv_bank = None if adversaries is None else adv_lib.adversary_bank(adversaries)
    adv_engaged = adv_lib.bank_engaged(adv_bank)
    n_edges = jnp.sum(jnp.asarray(adjacency)).astype(jnp.float32)

    def screen(w_hat, self_vals, cell):
        if neighbors is not None:
            return screening.screen_views_banked(
                neighbors.gather_rows(w_hat), neighbors.valid_dev, self_vals,
                rules, cell.rule_idx, cell.b, chunk=screen_chunk)
        return screening.screen_all_banked(
            w_hat, adjacency, rules, cell.rule_idx, cell.b, chunk=screen_chunk,
            self_vals=self_vals)

    def screen_decide(w_hat, self_vals, cell, stride, weights=None, evicted=None):
        # decision-instrumented twin: same y op graph (bitwise), plus the
        # [M, W] per-edge trim fractions the obs/trust aggregates fold in.
        # `weights`/`evicted` (repro.trust) thread reputation into the rules
        # and latched evictions into the mask; both None keeps the exact
        # trust-free call.
        if neighbors is not None:
            mask = neighbors.valid_dev if evicted is None else neighbors.valid_dev & ~evicted
            return screening.screen_views_decide_banked(
                neighbors.gather_rows(w_hat), mask, self_vals,
                rules, cell.rule_idx, cell.b, decide_stride=stride, weights=weights)
        adj = adjacency if evicted is None else jnp.asarray(adjacency, bool) & ~evicted
        return screening.screen_all_decide_banked(
            w_hat, adj, rules, cell.rule_idx, cell.b, self_vals=self_vals,
            decide_stride=stride, weights=weights)

    def step(cell: CellParams, state: BridgeState, batch: Any) -> tuple[BridgeState, dict]:
        spec = cell.trace  # static: TraceSpec or None (zero-leaf aux data)
        tspec = cell.trust  # static: TrustSpec or None (zero-leaf aux data)
        w, unflatten = stack_flatten(state.params)
        d = w.shape[1]
        key, sub = jax.random.split(state.key)
        # (Step 3-4) broadcast + Byzantine substitution of sent messages
        with jax.named_scope("bridge.attack"):
            w_bcast = byz_lib.apply_attack_bank(
                attacks, cell.attack_idx, w, cell.byz_mask, sub, state.t)
        new_adv = state.adv
        if adv_engaged:
            # the adaptive adversary observes the honest trajectory and
            # re-crafts the Byzantine rows; its screening oracle is this
            # cell's own banked screen (differentiable — inner maximization
            # ascends through it)
            with jax.named_scope("bridge.adversary"):
                ctx = adv_lib.AdvCtx(screen=lambda wb: screen(wb, wb, cell))
                theta = adv_lib.cell_theta(adv_bank, _cell_adv_idx(cell), cell.adv_theta)
                w_bcast, new_adv = adv_lib.apply_adversary_bank(
                    adv_bank, _cell_adv_idx(cell), ctx, state.adv, theta,
                    w_bcast, cell.byz_mask, jax.random.fold_in(sub, ADV_SALT), state.t,
                )
        # wire codec: what receivers actually decode (identity: w_bcast itself)
        with jax.named_scope("bridge.codec"):
            w_hat, new_comm = _wire_roundtrip(
                codec_bank, wire_attacks, cell, sub, w_bcast, state.comm,
                cell.byz_mask, state.t, d,
            )
        # (Step 5) screening at every node: neighbors are seen through the
        # wire; the node's own iterate never travels and stays uncompressed
        trim = None
        with jax.named_scope("bridge.screen"):
            if tspec is not None:
                # trust on: always the decide path (the trim fractions are
                # the evidence), reputation weights into the weighted rules,
                # evicted edges cleared from the mask
                from repro.trust import reputation as trust_lib

                screening.check_decide_streams(rules, d, screen_chunk)
                stride = (spec.decide_stride if spec is not None and spec.forensics
                          else tspec.decide_stride)
                y, trim = screen_decide(
                    w_hat, w_bcast, cell, stride,
                    weights=trust_lib.edge_weights(tspec, state.trust),
                    evicted=state.trust.evicted)
            elif spec is not None and spec.forensics:
                screening.check_decide_streams(rules, d, screen_chunk)
                y, trim = screen_decide(w_hat, w_bcast, cell, spec.decide_stride)
            else:
                y = screen(w_hat, w_bcast, cell)
        with jax.named_scope("bridge.apply"):
            new_params, metrics = _grad_update_and_metrics(
                grad_fn, cell, state, batch, y, unflatten)
        metrics.update(_comm_metrics(codec_bank, cell, d, n_edges, new_comm))
        new_obs = state.obs
        if spec is not None:
            from repro.obs import trace as obs_trace

            with jax.named_scope("bridge.obs"):
                live = byz_edge = None
                if trim is not None:
                    if neighbors is not None:
                        live = neighbors.valid_dev
                        byz_edge = neighbors.gather_senders(cell.byz_mask, fill=False)
                    else:
                        live = jnp.asarray(adjacency, bool)
                        byz_edge = jnp.broadcast_to(cell.byz_mask[None, :], live.shape)
                    live_f = live.astype(jnp.float32)
                    metrics["obs_trim_frac"] = (
                        jnp.sum(trim * live_f) / jnp.maximum(jnp.sum(live_f), 1.0))
                new_obs = obs_trace.update(
                    spec, state.obs, t=state.t, loss=metrics["loss"],
                    consensus=metrics["consensus_dist"], trim_frac=trim,
                    live=live, byz_edge=byz_edge, staleness=None,
                    wire_bits=comm_lib.wire_bits_bank(codec_bank, _cell_codec_idx(cell), d),
                    live_edges=n_edges, d=d)
        new_trust = state.trust
        if tspec is not None:
            from repro.trust import reputation as trust_lib

            with jax.named_scope("bridge.trust"):
                # no echo on the broadcast path: one payload per sender, so
                # equivocation is structurally impossible — trim evidence only
                if neighbors is not None:
                    live_t = neighbors.valid_dev & ~state.trust.evicted
                else:
                    live_t = jnp.asarray(adjacency, bool) & ~state.trust.evicted
                new_trust = trust_lib.update(
                    tspec, state.trust, t=state.t,
                    trim_frac=jnp.where(live_t, trim, 0.0), live=live_t)
                metrics["trust_evicted_frac"] = jnp.mean(
                    new_trust.evicted.astype(jnp.float32))
        new_mets = _fold_metric_ring(cell.metrics, state, metrics)
        return BridgeState(new_params, state.t + 1, key, state.net, new_comm,
                           new_adv, new_obs, new_trust, new_mets), metrics

    return step


def build_cell_runtime_step(grad_fn, runtime, rules: tuple[str, ...], message_attacks, *,
                            codecs: tuple[str, ...] = ("identity",), wire_attacks=None,
                            adversaries: tuple[str, ...] | None = None,
                            screen_chunk=None):
    """The network-runtime iteration: ``step(cell, state, batch)``.

    ``message_attacks`` is a static bank of `byzantine.MessageAttack`s and
    ``codecs`` / ``wire_attacks`` the wire-format banks (see
    `build_cell_step`).  Messages are encoded per *link* — a Byzantine sender
    tells different lies on different links, so its codewords (and the
    error-feedback residuals behind them) diverge per link too.  A runtime
    exposing ``cell_aware = True`` (the grid engine's scenario-banked
    runtime) additionally receives the cell so it can switch channel/schedule
    per experiment; the standard runtimes keep their two-argument contract.

    ``adversaries`` crafts per-link lies adaptively (`repro.adversary`): on a
    single-channel runtime the adversary additionally sees the coordinate
    subset a bandwidth-capped channel will deliver this tick and the
    channel's expected latency — the staleness-exploiting message variants.
    """
    cell_aware = bool(getattr(runtime, "cell_aware", False))
    # neighbor-indexed layout (repro.core.neighbors): the runtime exposes its
    # static table and every per-link tensor in this step is [M, K, ...]
    nbr = getattr(runtime, "neighbors", None)
    codec_bank = codec_lib.codec_bank(codecs)
    if wire_attacks is None:
        wire_attacks = (byz_lib.WIRE_ATTACKS["none"],) * len(message_attacks)
    adv_bank = None if adversaries is None else adv_lib.adversary_bank(adversaries)
    adv_engaged = adv_lib.bank_engaged(adv_bank)
    # omniscient channel knowledge is only well defined when the runtime has
    # ONE channel (the scenario-banked grid runtime switches per cell; its
    # adversaries fall back to attacking every coordinate, latency 0)
    channel = getattr(runtime, "channel", None)
    adv_latency = 0.0
    if channel is not None:
        adv_latency = 0.5 * (channel.latency_min + channel.latency_max)

    def screen_oracle(wb, adj_t, cell):
        """The adversary's differentiable per-tick screening closure."""
        if nbr is not None:
            return screening.screen_views_banked(
                nbr.gather_rows(wb), adj_t, wb, rules, cell.rule_idx, cell.b,
                chunk=screen_chunk)
        return screening.screen_all_banked(
            wb, adj_t, rules, cell.rule_idx, cell.b, chunk=screen_chunk,
            self_vals=wb)

    def step(cell: CellParams, state: BridgeState, batch: Any) -> tuple[BridgeState, dict]:
        spec = cell.trace  # static: TraceSpec or None (zero-leaf aux data)
        tspec = cell.trust  # static: TrustSpec or None (zero-leaf aux data)
        w, unflatten = stack_flatten(state.params)
        d = w.shape[1]
        m = w.shape[0]
        key, sub = jax.random.split(state.key)
        # dense: the tick's [M, M] adjacency; sparse: the [M, K] live-slot mask
        adj_t = runtime.adjacency_at(state.t, cell) if cell_aware else runtime.adjacency_at(state.t)
        # (Step 3-4) per-link transmissions with Byzantine substitution.
        with jax.named_scope("bridge.attack"):
            if nbr is not None:
                msgs = byz_lib.apply_sparse_message_attack_bank(
                    message_attacks, cell.attack_idx, w, cell.byz_mask, nbr, adj_t, sub, state.t
                )
            else:
                msgs = byz_lib.apply_message_attack_bank(
                    message_attacks, cell.attack_idx, w, cell.byz_mask, adj_t, sub, state.t
                )
            # Byzantine nodes screen with the same self-view they broadcast
            # (matching the synchronous path); message-only attacks have no
            # single broadcast value, so nodes screen with their true iterate.
            w_self = byz_lib.apply_self_view_bank(
                message_attacks, cell.attack_idx, w, cell.byz_mask, sub, state.t
            )
        new_adv = state.adv
        if adv_engaged:
            with jax.named_scope("bridge.adversary"):
                net_key_peek = jax.random.fold_in(sub, NET_SALT)
                deliver = None
                peek = getattr(runtime, "delivered_coord_mask", None)
                if peek is not None and not cell_aware:
                    deliver = peek(net_key_peek, d)
                ctx = adv_lib.AdvCtx(
                    screen=lambda wb: screen_oracle(wb, adj_t, cell),
                    deliver_mask=deliver,
                    latency=adv_latency,
                )
                theta = adv_lib.cell_theta(adv_bank, _cell_adv_idx(cell), cell.adv_theta)
                if nbr is not None:
                    adv_msgs, adv_self, new_adv = adv_lib.apply_sparse_message_adversary_bank(
                        adv_bank, _cell_adv_idx(cell), ctx, state.adv, theta,
                        w, cell.byz_mask, nbr, adj_t, jax.random.fold_in(sub, ADV_SALT), state.t,
                    )
                    adv_sender_byz = nbr.gather_senders(cell.byz_mask, fill=False)
                else:
                    adv_msgs, adv_self, new_adv = adv_lib.apply_message_adversary_bank(
                        adv_bank, _cell_adv_idx(cell), ctx, state.adv, theta,
                        w, cell.byz_mask, adj_t, jax.random.fold_in(sub, ADV_SALT), state.t,
                    )
                    adv_sender_byz = jnp.broadcast_to(cell.byz_mask[None, :], adj_t.shape)
                # the adversary re-crafts Byzantine senders only; honest links
                # keep whatever the static message-attack stage produced, bitwise
                msgs = jnp.where(adv_sender_byz[:, :, None], adv_msgs, msgs)
                w_self = jnp.where(cell.byz_mask[:, None], adv_self, w_self)
        # wire codec per link ([receiver, sender/slot] leading axes); the
        # sender axis marks whose codewords the wire attacks may corrupt, and
        # per-edge ids key their PRNG streams identically on both layouts
        if nbr is not None:
            byz_link = nbr.gather_senders(cell.byz_mask, fill=False)
            eids = nbr.edge_ids
        else:
            byz_link = jnp.broadcast_to(cell.byz_mask[None, :], adj_t.shape)
            eids = jnp.asarray(neighbors_lib.edge_id_grid(m))
        with jax.named_scope("bridge.codec"):
            msgs_hat, comm_full = _wire_roundtrip(
                codec_bank, wire_attacks, cell, sub, msgs, state.comm,
                byz_link, state.t, d, eids=eids,
            )
            if state.comm is not None and comm_full is not state.comm:
                # a sender advances a link's public copy / residual only for
                # messages actually put on the wire this tick (live edges);
                # channel drops are downstream and invisible to it
                comm_full = jax.tree_util.tree_map(
                    lambda new, old: jnp.where(adj_t[:, :, None], new, old),
                    comm_full, state.comm)
        wire_bits = comm_lib.wire_bits_bank(codec_bank, _cell_codec_idx(cell), d)
        net_key = jax.random.fold_in(sub, NET_SALT)
        with jax.named_scope("bridge.exchange"):
            if cell_aware:
                net, views, mask, net_stats = runtime.exchange(
                    state.net, msgs_hat, w_self, adj_t, net_key, state.t, cell,
                    wire_bits=wire_bits,
                )
            else:
                net, views, mask, net_stats = runtime.exchange(
                    state.net, msgs_hat, w_self, adj_t, net_key, state.t,
                    wire_bits=wire_bits,
                )
        # (Step 5) asynchronous screening over whatever usable (arrived,
        # fresh) messages each node holds; nodes starved below the rule's
        # minimum usable count keep their own iterate this tick.
        trim = None
        mask_eff = mask
        with jax.named_scope("bridge.screen"):
            if tspec is not None:
                # trust on: decide path (trim fractions are the evidence),
                # reputation weights into the weighted rules, evicted edges
                # cleared from the usable mask as if the link had died
                from repro.trust import reputation as trust_lib

                screening.check_decide_streams(rules, d, screen_chunk)
                stride = (spec.decide_stride if spec is not None and spec.forensics
                          else tspec.decide_stride)
                mask_eff = mask & ~state.trust.evicted
                y_rule, trim = screening.screen_views_decide_banked(
                    views, mask_eff, w_self, rules, cell.rule_idx, cell.b,
                    decide_stride=stride,
                    weights=trust_lib.edge_weights(tspec, state.trust),
                )
            elif spec is not None and spec.forensics:
                screening.check_decide_streams(rules, d, screen_chunk)
                y_rule, trim = screening.screen_views_decide_banked(
                    views, mask, w_self, rules, cell.rule_idx, cell.b,
                    decide_stride=spec.decide_stride,
                )
            else:
                y_rule = screening.screen_views_banked(
                    views, mask, w_self, rules, cell.rule_idx, cell.b, chunk=screen_chunk,
                )
            need = screening.min_neighbors_banked(rules, cell.rule_idx, cell.b)
            enough = jnp.sum(mask_eff, axis=1) >= need
            y = jnp.where(enough[:, None], y_rule, w_self)
        with jax.named_scope("bridge.apply"):
            new_params, metrics = _grad_update_and_metrics(
                grad_fn, cell, state, batch, y, unflatten)
        metrics.update(net_stats)
        metrics["screened_frac"] = jnp.mean(enough.astype(jnp.float32))
        metrics.update(_comm_metrics(
            codec_bank, cell, d, jnp.sum(adj_t).astype(jnp.float32), comm_full))
        new_obs = state.obs
        if spec is not None:
            from repro.obs import trace as obs_trace

            with jax.named_scope("bridge.obs"):
                live = byz_edge = None
                if trim is not None:
                    # nodes starved below the Table-II minimum fell back to
                    # their own iterate — their rows never screened this tick
                    # (mask_eff == mask when trust is off)
                    live = mask_eff & enough[:, None]
                    trim = jnp.where(live, trim, 0.0)
                    byz_edge = byz_link & live
                    live_f = live.astype(jnp.float32)
                    metrics["obs_trim_frac"] = (
                        jnp.sum(trim * live_f) / jnp.maximum(jnp.sum(live_f), 1.0))
                new_obs = obs_trace.update(
                    spec, state.obs, t=state.t, loss=metrics["loss"],
                    consensus=metrics["consensus_dist"], trim_frac=trim,
                    live=live, byz_edge=byz_edge,
                    staleness=obs_trace.staleness_of(net, state.t),
                    wire_bits=wire_bits,
                    live_edges=jnp.sum(adj_t).astype(jnp.float32), d=d)
        new_trust = state.trust
        if tspec is not None:
            from repro.trust import echo as echo_lib
            from repro.trust import reputation as trust_lib
            from repro.net import mailbox as mb

            echo_ev = None
            if tspec.echo:
                # (commit-then-gossip) digest what each node holds, exchange
                # digest rows one hop, and cross-check within matching send
                # generations — quorum-confirmed mismatches are equivocation
                with jax.named_scope("bridge.echo"):
                    trust_key = jax.random.fold_in(sub, TRUST_SALT)
                    gens = getattr(net, "send_tick", None)
                    if gens is None:
                        # net-less runtime (ideal synchronous exchange): every
                        # usable view was sent this tick
                        gens = jnp.where(mask, state.t, mb.NEVER)
                    if nbr is not None:
                        vals_d = echo_lib.scatter_dense(nbr, views, 0.0)
                        gens_d = echo_lib.scatter_dense(nbr, gens, mb.NEVER)
                        valid_d = echo_lib.scatter_dense(nbr, mask_eff, False)
                        gossip_d = echo_lib.scatter_dense(nbr, adj_t, False)
                    else:
                        vals_d, gens_d, valid_d = views, gens, mask_eff
                        gossip_d = jnp.asarray(adj_t, bool)
                    dig_d = echo_lib.digest_all(tspec, vals_d, trust_key)
                    if adv_engaged and adv_lib.bank_accuses(adv_bank):
                        # slanderers forge the digest rows they *report*
                        # (their own receptions stay honest — value screening
                        # sees nothing; only the gossip lies)
                        theta_acc = adv_lib.cell_theta(
                            adv_bank, _cell_adv_idx(cell), cell.adv_theta)
                        dig_d = adv_lib.apply_accuse_bank(
                            adv_bank, _cell_adv_idx(cell), theta_acc, dig_d,
                            cell.byz_mask, trust_key, state.t)
                    ev_d, _mism = echo_lib.equivocation_evidence(
                        dig_d, gens_d, valid_d, gossip_d, cell.b,
                        tol=tspec.echo_tol)
                    if nbr is not None:
                        echo_ev = nbr.gather_edges(ev_d, 0.0)
                    else:
                        echo_ev = ev_d
            with jax.named_scope("bridge.trust"):
                # rows starved below the rule minimum never screened: their
                # trim fractions are fallback artifacts, not evidence
                screened = mask_eff & enough[:, None]
                new_trust = trust_lib.update(
                    tspec, state.trust, t=state.t,
                    trim_frac=jnp.where(screened, trim, 0.0),
                    live=mask_eff, echo_evidence=echo_ev)
                metrics["trust_evicted_frac"] = jnp.mean(
                    new_trust.evicted.astype(jnp.float32))
        stale_m = None
        if cell.metrics is not None:
            from repro.obs import trace as obs_trace

            stale_m = obs_trace.staleness_of(net, state.t)
        new_mets = _fold_metric_ring(cell.metrics, state, metrics,
                                     staleness=stale_m, live=mask)
        return BridgeState(new_params, state.t + 1, key, net, comm_full,
                           new_adv, new_obs, new_trust, new_mets), metrics

    return step


def build_stream_cell_step(grad_fn, spec, adjacency, rules, attacks, **kwargs):
    """The chunk-streaming twin of `build_cell_step` /
    `build_cell_runtime_step`: the same attack -> codec -> (exchange ->)
    screen -> apply tick, executed per coordinate block of a parameter-pytree
    partition ``spec`` (`repro.stream.blocks.BlockSpec`) so the flat ``[M, d]``
    matrix of `stack_flatten` never materializes.  Thin delegator — the
    implementation lives in `repro.stream.engine` (imported lazily; the
    streaming subsystem imports this module for `BridgeState`/`CellParams`).
    """
    from repro.stream.engine import build_stream_cell_step as _impl

    return _impl(grad_fn, spec, adjacency, rules, attacks, **kwargs)


class BridgeTrainer:
    """Drives Algorithm 1.  ``grad_fn(node_params, batch) -> (loss, grads)``
    computes the *local* empirical-risk gradient of one node.

    ``runtime`` plugs in a message-exchange model (see `repro.net.runtime`):
    ``None`` is the classic synchronous broadcast simulation; an
    `UnreliableRuntime` yields asynchronous BRIDGE over a lossy, delayed,
    time-varying network, screening whatever messages have arrived (within
    the runtime's staleness bound) and falling back to the node's own iterate
    whenever too few usable messages are present for the rule's Table-II
    minimum.  With an ideal channel and a static schedule the runtime path
    reproduces the synchronous path bit-for-bit."""

    def __init__(self, config: BridgeConfig, grad_fn: Callable, runtime=None):
        config.topology.validate_for_rule(config.rule)
        self.config = config
        self.grad_fn = grad_fn
        self.runtime = runtime
        self.adjacency = jnp.asarray(config.topology.adjacency)
        m = config.topology.num_nodes
        nbyz = min(config.num_byzantine, m)
        if (config.attack == "none" and config.adversary == "none") or nbyz == 0:
            self.byz_mask = jnp.zeros((m,), dtype=bool)
        else:
            self.byz_mask = byz_lib.pick_byzantine_mask(m, nbyz, config.byzantine_seed)
        self.codec = codec_lib.get_codec(config.codec)
        wire_bank = byz_lib.wire_attack_bank((config.attack,))
        # the adversary bank is engaged only when named, so the default path
        # keeps its exact pre-adversary program shape
        self._adv_bank = (None if config.adversary == "none"
                          else adv_lib.adversary_bank((config.adversary,)))
        # the sync path's neighbor table (sparse layout); runtimes carry
        # their own (built from the schedule union)
        self.neighbors = None
        if config.sparse and runtime is None:
            self.neighbors = NeighborTable.from_adjacency(config.topology.adjacency)
        if config.sparse and runtime is not None and getattr(runtime, "neighbors", None) is None:
            raise ValueError(
                "BridgeConfig(sparse=True) with an explicit dense runtime: pass a "
                "neighbor-indexed runtime (SparseUnreliableRuntime) or drop the flag "
                "— a dense runtime would silently keep the O(M^2) state layout")
        if runtime is None:
            self._attack = byz_lib.get_attack(config.attack)
            step = build_cell_step(
                grad_fn, self.adjacency, (config.rule,), (self._attack,),
                codecs=(config.codec,), wire_attacks=wire_bank,
                adversaries=None if self._adv_bank is None else (config.adversary,),
                screen_chunk=config.screen_chunk, neighbors=self.neighbors,
            )
        else:
            self._message_attack = byz_lib.get_message_attack(config.attack)
            step = build_cell_runtime_step(
                grad_fn, runtime, (config.rule,), (self._message_attack,),
                codecs=(config.codec,), wire_attacks=wire_bank,
                adversaries=None if self._adv_bank is None else (config.adversary,),
                screen_chunk=config.screen_chunk,
            )
        # The cell rides along as a jit *operand*, not a closure constant, so
        # the compiled program is shape-identical to the batched grid engine's
        # (constant-folding a baked-in cell perturbs fusion at ULP level,
        # breaking the bit-for-bit grid<->trainer equivalence contract).
        self._cell = self.cell_params()
        self._raw_step = step
        self._jit_step = jax.jit(step)

    def cell_params(self) -> CellParams:
        """The constant single-cell parameters equivalent to this config
        (bank indices are 0 — the trainer's banks have one entry each)."""
        cfg = self.config
        adv_idx = adv_theta = None
        if self._adv_bank is not None:
            # theta rides as a jit operand (like the cell itself) for
            # program-shape parity with the grid engine
            adv_idx = jnp.zeros((), jnp.int32)
            adv_theta = jnp.asarray(self._adv_bank[0].default_theta, jnp.float32)
        return CellParams(
            rule_idx=jnp.zeros((), jnp.int32),
            attack_idx=jnp.zeros((), jnp.int32),
            b=jnp.asarray(cfg.num_byzantine, jnp.int32),
            byz_mask=self.byz_mask,
            lam=jnp.asarray(cfg.lam, jnp.float32),
            t0=jnp.asarray(cfg.t0, jnp.float32),
            lr=jnp.asarray(cfg.lr, jnp.float32),
            codec_idx=jnp.zeros((), jnp.int32),
            adv_idx=adv_idx,
            adv_theta=adv_theta,
            trace=cfg.trace,
            trust=cfg.trust,
            metrics=cfg.metrics,
        )

    @property
    def honest_mask(self) -> jax.Array:
        return ~self.byz_mask

    def init(self, params: Any, seed: int = 0) -> BridgeState:
        m = self.config.topology.num_nodes
        lead = jax.tree_util.tree_leaves(params)[0].shape[0]
        if lead != m:
            raise ValueError(f"params leading axis {lead} != num_nodes {m}")
        net = comm = adv = None
        w, _ = stack_flatten(params)
        dim = w.shape[1]
        if self.runtime is not None:
            net = self.runtime.init(m, dim, max_wire_bits=self.codec.wire_bits(dim))
            # per-link codec carry: [M, M, d] dense, [M, K, d] neighbor-indexed
            rt_nbr = getattr(self.runtime, "neighbors", None)
            link = m if rt_nbr is None else rt_nbr.k
            comm = comm_lib.init_residual((m, link, dim), (self.codec,))
        else:
            comm = comm_lib.init_residual((m, dim), (self.codec,))
        if adv_lib.bank_stateful(self._adv_bank):
            adv = adv_lib.init_state(dim)
        obs = trust = None
        nbr = (self.neighbors if self.runtime is None
               else getattr(self.runtime, "neighbors", None))
        width = m if nbr is None else nbr.k
        if self.config.trace is not None:
            from repro.obs import trace as obs_trace

            obs = obs_trace.init_state(self.config.trace, m, width)
        if self.config.trust is not None:
            from repro.trust import reputation as trust_lib

            trust = trust_lib.init_state(self.config.trust, m, width)
        mets = None
        if self.config.metrics is not None:
            from repro.obs import metrics as obs_metrics

            mets = obs_metrics.init_state(self.config.metrics)
        return BridgeState(params=params, t=jnp.zeros((), jnp.int32),
                           key=jax.random.PRNGKey(seed), net=net, comm=comm,
                           adv=adv, obs=obs, trust=trust, mets=mets)

    def step(self, state: BridgeState, batch: Any) -> tuple[BridgeState, dict]:
        return self._jit_step(self._cell, state, batch)

    def run(self, state: BridgeState, batch_fn: Callable[[int], Any], num_steps: int,
            eval_fn: Callable | None = None, eval_every: int = 0) -> tuple[BridgeState, list[dict]]:
        history = []
        for i in range(num_steps):
            state, metrics = self.step(state, batch_fn(i))
            if eval_fn is not None and eval_every and (i + 1) % eval_every == 0:
                metrics = dict(metrics)
                metrics.update(eval_fn(state))
                metrics["step"] = i + 1
                history.append(jax.device_get(metrics))
        return state, history

    # -- chunked host loop (the live-telemetry / grid-throughput hook) ------

    def _chunk_scan(self):
        """The jitted scan-over-one-chunk with a DONATED state carry.  jax
        caches compilations per chunk length, so a run costs one trace for
        the full-width chunks plus one for a ragged tail."""
        fn = getattr(self, "_chunk_scan_fn", None)
        if fn is None:
            raw = self._raw_step

            def scan_chunk(cell, st, xs):
                # Python side effect: executes only while tracing — the
                # retrace guard (`repro.analysis.retrace`) reads this counter
                # to prove a run cost one trace per distinct chunk length
                self.chunk_trace_count = getattr(self, "chunk_trace_count", 0) + 1
                return jax.lax.scan(lambda s, b: raw(cell, s, b), st, xs)

            fn = self._chunk_scan_fn = jax.jit(scan_chunk, donate_argnums=(1,))
        return fn

    def run_chunks(self, state: BridgeState, batch_fn: Callable[[int], Any],
                   num_steps: int, *, chunk: int | None = None, writer=None,
                   events=None, tag: str = "train",
                   start: int = 0) -> tuple[BridgeState, dict]:
        """Run ``num_steps`` ticks as a host loop over jitted scan *chunks*
        with donated carries — dispatch never waits for host I/O.

        After each chunk the live-metric ring is handed to ``writer``
        (`repro.obs.metrics.MetricWriter` — which copies it device-side
        before the next dispatch invalidates the donated buffer) and a
        ``train.chunk`` record lands in ``events``.  ``chunk`` defaults to
        the metric spec's ring capacity (no tick overwritten before it is
        flushed), or 64 without one.  Returns ``(final_state, metrics)``
        with ``[T]`` metric streams, bitwise identical to step-at-a-time /
        single-scan execution (pinned by ``tests/test_metrics.py``).

        The loop writes ``jax.profiler`` spans (about a microsecond each
        when no profiler runs): ``bridge.run_chunks`` (args ``lo``, ``hi``)
        around the call and, per chunk, ``bridge.put`` (``bytes`` put,
        ``ticks``), ``bridge.stack`` (``ticks``), ``bridge.dispatch``
        (``lo``, ``hi``, ``traced``: 1 if the call traced the scan anew),
        ``bridge.flush`` (writer and events, when given), then one
        ``bridge.collect`` for the concatenation of the metric chunks.
        """
        mspec = getattr(self.config, "metrics", None)
        if chunk is None:
            chunk = mspec.capacity if mspec is not None else 64
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        if mspec is not None and chunk > mspec.capacity:
            raise ValueError(
                f"chunk {chunk} exceeds MetricSpec.capacity {mspec.capacity}: "
                f"the ring would overwrite unflushed ticks")
        scan_chunk = self._chunk_scan()
        ann = jax.profiler.TraceAnnotation
        chunks_ms = []
        done = start
        with ann("bridge.run_chunks", lo=start, hi=start + num_steps):
            while done < start + num_steps:
                hi = min(done + chunk, start + num_steps)
                batches = [batch_fn(i) for i in range(done, hi)]
                with ann("bridge.put", bytes=host_nbytes(batches), ticks=hi - done):
                    puts = put_batches(batches)
                with ann("bridge.stack", ticks=hi - done):
                    xs = stack_puts(puts)
                traces = getattr(self, "chunk_trace_count", 0)
                with ann("bridge.dispatch", lo=done, hi=hi) as span:
                    state, ms = scan_chunk(self._cell, state, xs)
                    span.set_metadata(
                        traced=int(getattr(self, "chunk_trace_count", 0) > traces))
                # host work below overlaps the dispatched device computation:
                # the writer copies the ring and device_gets on its own thread
                if writer is not None or events is not None:
                    with ann("bridge.flush"):
                        if writer is not None:
                            writer.flush(state.mets, tag=tag)
                        if events is not None:
                            # `train_tag`, not `tag`: EventLog.emit's first
                            # argument IS the record's "tag" field and fields
                            # must not collide
                            events.emit("train.chunk", train_tag=tag, lo=done, hi=hi)
                chunks_ms.append(ms)
                done = hi
            with ann("bridge.collect"):
                metrics = jax.tree_util.tree_map(
                    lambda *xs: jnp.concatenate(xs, axis=0), *chunks_ms)
        return state, metrics


def replicate(params: Any, num_nodes: int, *, perturb: float = 0.0, key=None) -> Any:
    """Stack one model into [M, ...] node replicas; optional init perturbation
    (the paper initializes nodes inside a common ball, not identically —
    unlike ICwTM which *requires* identical initialization)."""

    def rep(leaf):
        return jnp.broadcast_to(leaf[None], (num_nodes,) + leaf.shape)

    stacked = jax.tree_util.tree_map(rep, params)
    if perturb > 0.0:
        if key is None:
            key = jax.random.PRNGKey(0)
        leaves, treedef = jax.tree_util.tree_flatten(stacked)
        keys = jax.random.split(key, len(leaves))
        leaves = [
            l + perturb * jax.random.normal(k, l.shape, l.dtype)
            for l, k in zip(leaves, keys, strict=True)
        ]
        stacked = jax.tree_util.tree_unflatten(treedef, leaves)
    return stacked


# ---------------------------------------------------------------------------
# static-analysis contracts (checked by `python -m repro.analysis`)
# ---------------------------------------------------------------------------

from repro.analysis.contracts import Contract  # noqa: E402  (dependency-light)

CONTRACTS: tuple[Contract, ...] = (
    Contract(
        "bridge.prng.single_use", "prng",
        "no PRNG key in a compiled step feeds two distinct random draws "
        "without an intervening split/fold_in — per-edge wire-roundtrip and "
        "per-step subkey independence, statically (flat, sparse, net, and "
        "metrics-on canonical programs)",
        params=(("programs", ("flat", "sparse", "net", "metrics")),),
    ),
    Contract(
        "bridge.salts.distinct", "lint",
        "the stream salts (attack / channel / codec / wire / adversary / "
        "trust) are pairwise distinct, so streams folded from one step "
        "subkey never correlate",
        params=(("check", "salts_distinct"),
                ("salts", ("NET_SALT", "COMM_SALT", "WIRE_SALT", "ADV_SALT",
                           "TRUST_SALT"))),
    ),
    Contract(
        "bridge.sparse.no_dense_mmd", "memory",
        "the sparse (neighbor-indexed) step never materializes a tensor as "
        "large as the dense [M, M, d] float layout it replaces",
        params=(("programs", ("sparse",)), ("budget", "dense_mmd")),
    ),
    Contract(
        "bridge.run_chunks.single_trace", "retrace",
        "a uniform-chunk run_chunks costs exactly one trace, and an "
        "identically-shaped re-run costs zero (compilations are cached per "
        "chunk length)",
        params=(("max_traces", 1),),
    ),
    Contract(
        "bridge.chunk_carry.donated", "memory",
        "the chunk scan's donated state carry survives into the compiled "
        "module's input_output_alias table (donation honored, not silently "
        "copied)",
        params=(("check", "donation"),),
    ),
)
