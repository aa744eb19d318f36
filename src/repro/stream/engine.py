"""The chunk-streaming BRIDGE iteration: screen parameter pytrees block by
block, never materializing the flat ``[M, d]`` matrix.

One tick runs the same phases as `repro.core.bridge.build_cell_step` —
attack -> codec -> (exchange ->) screen -> apply -> obs/trust — but the
attack/codec/screen/apply phases execute *inside* a per-leaf loop over
coordinate blocks (`repro.stream.blocks.BlockSpec`): full-width blocks ride a
``lax.scan``, each leaf's tail block runs inline at its exact size, and every
block's screened update is written straight into that leaf's output buffer in
the leaf's own storage dtype.  Peak live state in the loop is ``[M, K, c]``
(one gathered block) plus the model's own leaves — at LLM ``d`` the flat
path's ``[M, d]`` f32 broadcast/screen tensors simply never exist.  ByRDiE
(arXiv:1708.08155) already updated coordinate-by-coordinate, so blockwise
BRIDGE screening is the algorithm family's native decomposition, not an
approximation: for the coordinate-wise rules (`screening.STREAMABLE_RULES`)
the result is *bitwise* the flat path's.

Bit-identity contract (pinned by ``tests/test_stream.py``):

* **Single block** (one leaf, ``chunk >= d``): the per-block PRNG key is the
  step subkey itself, so the full rule x attack x codec product — including
  stochastic attacks and stochastic-rounding codecs — matches the flat
  trainer bit-for-bit.
* **Many blocks**: block i folds ``i`` into the subkey (independent streams
  per block), so draws differ from the flat path's single full-width draw by
  construction; every *deterministic* attack/codec combination still matches
  bitwise, because the coordinate-wise rules, the per-coordinate attacks, and
  `screening.fence` all decompose exactly over blocks.  Stochastic combos are
  distributionally equivalent, not bitwise.

Codecs apply per block (`repro.comm.exchange.wire_bits_blocks`): each block
is an independent codeword with its own error-feedback slice, so top-k keeps
k coordinates *per block* and per-message overhead is paid per block — the
honest accounting for a chunked wire.

The optional network path replaces the ideal broadcast with a per-edge
drop/staleness channel over `repro.net.mailbox.BlockMailboxState`: one
arrival event per edge per tick (all blocks of a message travel together),
per-block payload writes, Table-II min-usable fallback.  With an ideal
channel (``drop_prob=0``) it reproduces the streaming broadcast path
bit-for-bit wherever every node clears the rule's usable minimum.

Not supported while streaming: vector rules (krum/bulyan/geomedian/
clipped_mean — their outputs depend on full-vector norms), adaptive
adversaries (omniscient crafting wants the full flat trajectory), and the
echo protocol (digests commit to whole messages); all three raise at build
time rather than silently changing semantics.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.comm import codec as codec_lib
from repro.comm import exchange as comm_lib
from repro.core import byzantine as byz_lib
from repro.core import screening
from repro.core.bridge import (
    COMM_SALT,
    NET_SALT,
    WIRE_SALT,
    BridgeState,
    CellParams,
    _cell_codec_idx,
    _fold_metric_ring,
    cell_step_size,
)
from repro.core.neighbors import NeighborTable
from repro.net import mailbox as mb
from repro.stream.blocks import BlockSpec


@dataclasses.dataclass(frozen=True)
class StreamChannelConfig:
    """The streaming network path's channel: per-receiver message drops over
    a broadcast medium (every neighbor of a sender sees the *same* codeword;
    whether it arrives is per edge), with a staleness bound on what screening
    may still consume.  ``drop_prob=0`` is the ideal channel — bit-identical
    to the streaming broadcast path where in-degrees clear the rule minimum."""

    drop_prob: float = 0.0
    staleness_bound: int = 4


def build_stream_cell_step(grad_fn, spec: BlockSpec, adjacency, rules, attacks, *,
                           codecs=("identity",), wire_attacks=None,
                           neighbors: NeighborTable | None = None,
                           channel: StreamChannelConfig | None = None):
    """The streaming twin of `build_cell_step` (``channel=None``) and of the
    runtime path (``channel`` set): ``step(cell, state, batch)`` over the
    block partition ``spec``.  The network path requires ``neighbors`` (its
    mailbox width is K) and a `BlockMailboxState` in ``state.net``."""
    screening.check_streamable(rules)
    if channel is not None and neighbors is None:
        raise ValueError("the streaming network path is neighbor-indexed: "
                         "pass a NeighborTable")
    codec_bank = codec_lib.codec_bank(codecs)
    if wire_attacks is None:
        wire_attacks = (byz_lib.WIRE_ATTACKS["none"],) * len(attacks)
    skip_wire = (comm_lib.bank_is_lossless(codec_bank)
                 and all(a.name == "none" for a in wire_attacks))
    adjacency = None if adjacency is None else jnp.asarray(adjacency)
    n_edges = (jnp.sum(neighbors.valid_dev).astype(jnp.float32)
               if neighbors is not None
               else jnp.sum(adjacency).astype(jnp.float32))
    m = spec.num_nodes
    d = spec.total_dim
    single_block = spec.num_blocks == 1

    def step(cell: CellParams, state: BridgeState, batch: Any) -> tuple[BridgeState, dict]:
        tr_spec = cell.trace  # static: TraceSpec or None
        tspec = cell.trust  # static: TrustSpec or None
        decide = tspec is not None or (tr_spec is not None and tr_spec.forensics)
        cidx = _cell_codec_idx(cell)
        key, sub = jax.random.split(state.key)
        with jax.named_scope("stream.grad"):
            losses, grads = jax.vmap(grad_fn)(state.params, batch)
        rho = cell_step_size(cell, state.t)
        with jax.named_scope("stream.layout"):
            x_mats = spec.leaf_mats(state.params)
            g_mats = spec.leaf_mats(grads)
        hm = ~cell.byz_mask
        hcnt = jnp.sum(hm)

        weights = evicted = None
        stride = 1
        if tspec is not None:
            from repro.trust import reputation as trust_lib

            weights = trust_lib.edge_weights(tspec, state.trust)
            evicted = state.trust.evicted
            stride = (tr_spec.decide_stride
                      if tr_spec is not None and tr_spec.forensics
                      else tspec.decide_stride)
        elif decide:
            stride = tr_spec.decide_stride

        # live-edge structure (static topology on both paths)
        if neighbors is not None:
            valid = neighbors.valid_dev  # [M, K]
            byz_edge_all = neighbors.gather_senders(cell.byz_mask, fill=False)
        else:
            valid = jnp.asarray(adjacency, bool)  # [M, M]
            byz_edge_all = jnp.broadcast_to(cell.byz_mask[None, :], valid.shape)

        # network path: one channel event per edge per tick, shared by every
        # coordinate block of the tick's message
        arrived = send_tick = enough = None
        if channel is not None:
            net_key = jax.random.fold_in(sub, NET_SALT)
            u = jax.random.uniform(net_key, valid.shape)
            arrived = valid & (u >= channel.drop_prob)
            send_tick = mb.stamp(state.net.send_tick, arrived, state.t)
            usable = valid & (send_tick > mb.NEVER) & (
                send_tick >= state.t - channel.staleness_bound)
            mask_live = usable
        else:
            mask_live = valid
        mask_eff = mask_live if evicted is None else mask_live & ~evicted
        if channel is not None:
            need = screening.min_neighbors_banked(rules, cell.rule_idx, cell.b)
            enough = jnp.sum(mask_eff, axis=1) >= need  # [M]
            obs_live = mask_eff & enough[:, None]
        else:
            obs_live = valid
        obs_live_f = obs_live.astype(jnp.float32)
        # dense broadcast screening consumes the adjacency operand directly
        # (bitwise parity with build_cell_step's trust-on/off calls)
        dense_adj = None
        if neighbors is None:
            dense_adj = adjacency if evicted is None else valid & ~evicted

        def block_fn(x2d, g2d, carry, gid, start, size):
            """One coordinate block through attack -> codec -> (exchange ->)
            screen -> apply; ``start`` may be traced (scan) or static (tail),
            ``size`` is always static."""
            y_buf, comm_leaf, vals_leaf, trim_acc, cons_sq = carry
            kb = sub if single_block else jax.random.fold_in(sub, gid)
            xb = jax.lax.dynamic_slice(x2d, (0, start), (m, size)).astype(jnp.float32)
            with jax.named_scope("stream.attack"):
                wb = byz_lib.apply_attack_bank(
                    attacks, cell.attack_idx, xb, cell.byz_mask, kb, state.t)
            with jax.named_scope("stream.codec"):
                if skip_wire:
                    what, comm_new = wb, comm_leaf
                else:
                    comm_blk = None if comm_leaf is None else jax.tree_util.tree_map(
                        lambda a: jax.lax.dynamic_slice(a, (0, start), (m, size)),
                        comm_leaf)
                    ck = jax.random.fold_in(kb, COMM_SALT)
                    wk = jax.random.fold_in(kb, WIRE_SALT)
                    msg, target = comm_lib.encode_bank(codec_bank, cidx, ck, wb, comm_blk)
                    msg = byz_lib.apply_wire_attack_bank(
                        wire_attacks, cell.attack_idx, msg, cell.byz_mask, wk,
                        state.t, size)
                    what, comm_blk_new = comm_lib.decode_bank(
                        codec_bank, cidx, msg, target, comm_blk, ck)
                    comm_new = comm_leaf if comm_leaf is None else jax.tree_util.tree_map(
                        lambda full, blk: jax.lax.dynamic_update_slice(full, blk, (0, start)),
                        comm_leaf, comm_blk_new)
            if channel is not None:
                with jax.named_scope("stream.exchange"):
                    msgs_blk = neighbors.gather_rows(what)  # [M, K, size]
                    vals_leaf = mb.push_block(vals_leaf, msgs_blk, arrived, start)
                    views = jax.lax.dynamic_slice(
                        vals_leaf, (0, 0, start), (m, neighbors.k, size))
            trim_b = None
            with jax.named_scope("stream.screen"):
                if channel is not None:
                    if decide:
                        y_b, trim_b = screening.screen_views_decide_banked(
                            views, mask_eff, wb, rules, cell.rule_idx, cell.b,
                            decide_stride=stride, weights=weights)
                    else:
                        y_b = screening.screen_views_banked(
                            views, mask_eff, wb, rules, cell.rule_idx, cell.b,
                            chunk=None)
                    # nodes starved below the Table-II minimum keep their own
                    # (broadcast) iterate this tick — same fallback, per block
                    y_b = jnp.where(enough[:, None], y_b, wb)
                elif neighbors is not None:
                    gathered = neighbors.gather_rows(what)
                    if decide:
                        y_b, trim_b = screening.screen_views_decide_banked(
                            gathered, mask_eff, wb, rules, cell.rule_idx, cell.b,
                            decide_stride=stride, weights=weights)
                    else:
                        y_b = screening.screen_views_banked(
                            gathered, mask_eff, wb, rules, cell.rule_idx, cell.b,
                            chunk=None)
                else:
                    if decide:
                        y_b, trim_b = screening.screen_all_decide_banked(
                            what, dense_adj, rules, cell.rule_idx, cell.b,
                            self_vals=wb, decide_stride=stride, weights=weights)
                    else:
                        y_b = screening.screen_all_banked(
                            what, dense_adj, rules, cell.rule_idx, cell.b,
                            chunk=None, self_vals=wb)
            with jax.named_scope("stream.apply"):
                gb = jax.lax.dynamic_slice(g2d, (0, start), (m, size)).astype(jnp.float32)
                w_new = y_b - screening.fence(rho * gb)
                y_buf = jax.lax.dynamic_update_slice(
                    y_buf, w_new.astype(y_buf.dtype), (0, start))
                mu = jnp.sum(jnp.where(hm[:, None], w_new, 0.0), axis=0) / hcnt
                dev = jnp.where(hm[:, None], w_new - mu[None, :], 0.0)
                cons_sq = cons_sq + jnp.sum(dev * dev, axis=1)
            ys = None
            if decide:
                from repro.trust import reputation as trust_lib

                trim_acc = trust_lib.accumulate_trim(trim_acc, trim_b, size / d)
                ys = (jnp.sum(trim_b * obs_live_f)
                      / jnp.maximum(jnp.sum(obs_live_f), 1.0))
            return (y_buf, comm_new, vals_leaf, trim_acc, cons_sq), ys

        width = valid.shape[1]
        trim_acc = jnp.zeros((m, width), jnp.float32) if decide else None
        cons_sq = jnp.zeros((m,), jnp.float32)
        comm_list = ((None,) * len(spec.leaves) if state.comm is None
                     else tuple(state.comm))
        vals_list = (tuple(state.net.values) if channel is not None
                     else (None,) * len(spec.leaves))
        mats_out, comm_out, vals_out, block_trims = [], [], [], []
        for li, plan in enumerate(spec.leaves):
            x2d, g2d = x_mats[li], g_mats[li]
            c = min(spec.chunk, plan.size)
            # every coordinate belongs to exactly one block, so the buffer is
            # fully overwritten; seeding it with the input keeps dtype/shape
            carry = (x2d, comm_list[li], vals_list[li], trim_acc, cons_sq)
            if plan.num_full == 1:
                carry, ys = block_fn(x2d, g2d, carry, plan.block0, 0, c)
                if decide:
                    block_trims.append(ys[None])
            elif plan.num_full > 1:
                gids = plan.block0 + jnp.arange(plan.num_full, dtype=jnp.int32)
                starts = jnp.arange(plan.num_full, dtype=jnp.int32) * c

                def body(cr, gs, x2d=x2d, g2d=g2d, c=c):
                    return block_fn(x2d, g2d, cr, gs[0], gs[1], c)

                carry, ys = jax.lax.scan(body, carry, (gids, starts))
                if decide:
                    block_trims.append(ys)
            if plan.tail:
                carry, ys = block_fn(x2d, g2d, carry,
                                     plan.block0 + plan.num_full,
                                     plan.num_full * c, plan.tail)
                if decide:
                    block_trims.append(ys[None])
            y_buf, comm_leaf, vals_leaf, trim_acc, cons_sq = carry
            mats_out.append(y_buf)
            comm_out.append(comm_leaf)
            vals_out.append(vals_leaf)

        with jax.named_scope("stream.layout"):
            new_params = spec.unflatten(mats_out)
        new_comm = None if state.comm is None else tuple(comm_out)
        new_net = state.net
        if channel is not None:
            new_net = mb.BlockMailboxState(send_tick=send_tick,
                                           values=tuple(vals_out))
        metrics = {
            "loss": jnp.sum(jnp.where(hm, losses, 0.0)) / hcnt,
            "consensus_dist": jnp.sqrt(jnp.max(cons_sq)),
            "rho": rho,
        }
        if cell.metrics is not None:
            # honest-mean per-node gradient norm for the live-metric ring;
            # summed leaf-wise so the flat [M, d] matrix never materializes.
            # Each leaf goes through the fence first: the squares would
            # otherwise CSE with the loss computation inside grad_fn and
            # re-fuse its reduction — ULP-shifting the loss stream and
            # breaking metrics-on bit-inertness
            gn_sq = sum(jnp.sum(jnp.square(screening.fence(
                            g.astype(jnp.float32))), axis=1)
                        for g in g_mats)
            gn = jnp.sqrt(gn_sq)
            metrics["grad_norm"] = jnp.sum(jnp.where(hm, gn, 0.0)) / hcnt
        bits = comm_lib.wire_bits_blocks(codec_bank, cidx, spec.block_sizes())
        live_edges = (jnp.sum(mask_live).astype(jnp.float32)
                      if channel is not None else n_edges)
        metrics["wire_bits_per_edge"] = jnp.asarray(bits, jnp.float32)
        metrics["wire_bytes_total"] = metrics["wire_bits_per_edge"] / 8.0 * live_edges
        metrics["ef_residual_norm"] = (
            jnp.zeros((), jnp.float32) if new_comm is None else jnp.sqrt(sum(
                jnp.sum(cst.resid * cst.resid) for cst in new_comm)))
        if channel is not None:
            metrics["delivered_frac"] = (jnp.sum(arrived.astype(jnp.float32))
                                         / jnp.maximum(n_edges, 1.0))
            stale = jnp.where(mask_live, state.t - send_tick, 0)
            metrics["mean_staleness"] = (jnp.sum(stale.astype(jnp.float32))
                                         / jnp.maximum(jnp.sum(mask_live), 1))
            metrics["screened_frac"] = jnp.mean(enough.astype(jnp.float32))
            metrics["usable_in"] = jnp.mean(jnp.sum(mask_eff, axis=1).astype(jnp.float32))
        if decide:
            from repro.obs import trace as obs_trace

            metrics["obs_trim_frac"] = (
                jnp.sum(trim_acc * obs_live_f)
                / jnp.maximum(jnp.sum(obs_live_f), 1.0))
            metrics[obs_trace.BLOCK_TRIM_STREAM] = jnp.concatenate(block_trims)
        new_obs = state.obs
        if tr_spec is not None:
            from repro.obs import trace as obs_trace

            with jax.named_scope("stream.obs"):
                trim_o = live_o = byz_o = None
                if decide:
                    live_o = obs_live
                    trim_o = (jnp.where(live_o, trim_acc, 0.0)
                              if channel is not None else trim_acc)
                    byz_o = (byz_edge_all & live_o if channel is not None
                             else byz_edge_all)
                stale_o = None
                if channel is not None:
                    stale_o = obs_trace.staleness_of(new_net, state.t)
                new_obs = obs_trace.update(
                    tr_spec, state.obs, t=state.t, loss=metrics["loss"],
                    consensus=metrics["consensus_dist"], trim_frac=trim_o,
                    live=live_o, byz_edge=byz_o, staleness=stale_o,
                    wire_bits=bits, live_edges=live_edges, d=d)
        new_trust = state.trust
        if tspec is not None:
            from repro.trust import reputation as trust_lib

            with jax.named_scope("stream.trust"):
                if channel is not None:
                    screened = mask_eff & enough[:, None]
                    new_trust = trust_lib.update(
                        tspec, state.trust, t=state.t,
                        trim_frac=jnp.where(screened, trim_acc, 0.0),
                        live=mask_eff)
                else:
                    new_trust = trust_lib.update(
                        tspec, state.trust, t=state.t,
                        trim_frac=jnp.where(mask_eff, trim_acc, 0.0),
                        live=mask_eff)
                metrics["trust_evicted_frac"] = jnp.mean(
                    new_trust.evicted.astype(jnp.float32))
        stale_m = live_m = None
        if cell.metrics is not None and channel is not None:
            stale_m = jnp.where(mask_live, state.t - send_tick, 0)
            live_m = mask_live
        new_mets = _fold_metric_ring(cell.metrics, state, metrics,
                                     staleness=stale_m, live=live_m)
        return BridgeState(new_params, state.t + 1, key, new_net, new_comm,
                           state.adv, new_obs, new_trust, new_mets), metrics

    return step


# ---------------------------------------------------------------------------
# static-analysis contracts (checked by `python -m repro.analysis`)
# ---------------------------------------------------------------------------

from repro.analysis.contracts import Contract  # noqa: E402  (dependency-light)

CONTRACTS: tuple[Contract, ...] = (
    Contract(
        "stream.peak_memory.flat_bound", "memory",
        "the streaming step's largest tensor is strictly smaller than the "
        "flat [M, d] float matrix it exists to avoid (peak live state is "
        "one gathered [M, K, c] block plus the model's own leaves)",
        params=(("programs", ("stream",)), ("budget", "flat_md")),
    ),
    Contract(
        "stream.prng.per_block_keys", "prng",
        "every block draws from its own folded key (block i folds i into "
        "the step subkey): no key feeds two distinct draws anywhere in the "
        "streaming program",
        params=(("programs", ("stream",)),),
    ),
)
