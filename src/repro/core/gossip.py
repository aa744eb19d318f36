"""Sharded gossip + screening for the TPU mesh execution path.

The node axis of every parameter leaf ``[M, ...]`` is sharded over the mesh's
node axes (``("data",)`` single-pod, ``("pod","data")`` multi-pod); the
remaining dims are tensor-parallel over ``"model"``.  Screening therefore
operates per chip on that chip's coordinate shard — coordinate-wise rules
(BRIDGE-T/M, the analyzed variants) are embarrassingly parallel across
coordinates, so *no cross-"model" communication is needed at all*; only the
node axis communicates.

Two collective schedules (the subject of §Perf iteration 1):

* ``all_gather`` — paper-faithful broadcast: every chip all-gathers all M
  node values of its shard (M*P bytes on the wire per step) and screens its
  own node's row.  The gathered leaf keeps its own shape ``[M, ...]`` and
  the rule runs on it as it is: a flattened ``[M, s]`` view costs a
  relayout copy on the TPU's tiled layouts and puts the M node rows on the
  sublanes of the same tiles.
* ``all_to_all`` — beyond-paper coordinate-partitioned schedule: each chip's
  shard is split into M coordinate chunks; a first all_to_all transposes
  (node, chunk) ownership, every chip screens its chunk **for all M
  receivers**, a second all_to_all transposes back (2*P bytes on the wire).
  Valid because BRIDGE-T/M are coordinate-separable (Sec. III: "the
  calculation of y_j(t) has to be carried out in a coordinate-wise manner").

Both schedules screen with the same rules (`_COORD_RULES`): the trimmed mean
without the XLA-CPU anchor of `screening.trimmed_mean` (on a whole gathered
leaf it would run the sort network a second time), bitwise its output.

Vector rules (BRIDGE-K/B) need global inter-replica distances; those are
computed with pure-GSPMD reductions (per-leaf partial Gram matrices that XLA
turns into reduce-scatter/all-reduce over "model") followed by a node-axis
gather of the selected replicas.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import screening

_COORD_RULES = {
    "trimmed_mean": screening.trimmed_mean_unanchored,
    "median": screening.coordinate_median,
    "mean": screening.mean,
}


def _inject_attack(vals, byz_mask, attack, key, t, node_index):
    """Substitute Byzantine rows of the gathered values [M, ...]."""
    if attack == "none" or byz_mask is None:
        return vals
    if attack == "random":
        k = jax.random.fold_in(jax.random.fold_in(key, t), node_index)
        noise = 10.0 * jax.random.normal(k, vals.shape, vals.dtype)
        return jnp.where(screening.per_row(byz_mask, vals), noise, vals)
    if attack == "sign_flip":
        return jnp.where(screening.per_row(byz_mask, vals), -4.0 * vals, vals)
    raise ValueError(f"attack {attack!r} not supported on the sharded path")


def _quantize_int8(x):
    """Per-tensor-chunk symmetric int8 quantization.  Monotone per coordinate
    (single shared positive scale), so rank-based screening (trimmed mean /
    median survivor SETS) is exactly preserved; only the averaged magnitudes
    carry quantization error.  Returns (q int8, scale f32)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)))
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale


def coordwise_gossip_leaf(
    leaf: jax.Array,
    spec: P,
    *,
    mesh: jax.sharding.Mesh,
    node_axes,
    rule: str,
    b: int,
    adjacency: jax.Array,
    schedule: str = "all_gather",
    byz_mask: jax.Array | None = None,
    attack: str = "none",
    key: jax.Array | None = None,
    t: jax.Array | int = 0,
    quantize: bool = False,
) -> jax.Array:
    """Screen one [M, ...] parameter leaf with a coordinate-wise rule."""
    fn = _COORD_RULES[rule]
    m = leaf.shape[0]
    if key is None:
        key = jax.random.PRNGKey(0)
    t = jnp.asarray(t, jnp.int32)
    if byz_mask is None:
        byz_mask = jnp.zeros((m,), dtype=bool)

    def ag_body(x, adj, bm, k, tt):
        with jax.named_scope("mesh.gather"):
            if quantize:
                q, scale = _quantize_int8(x)
                gq = lax.all_gather(q, node_axes, axis=0, tiled=True)  # int8 wire
                gs = lax.all_gather(scale[None], node_axes, axis=0, tiled=True)
                g = gq.astype(jnp.float32) * screening.per_row(gs, gq)
            else:
                g = lax.all_gather(x, node_axes, axis=0, tiled=True)  # [M, ...]
        j = lax.axis_index(node_axes)
        g = _inject_attack(g, bm, attack, k, tt, j)
        with jax.named_scope("mesh.screen"):
            y = fn(g, adj[j], g[j], b)  # own-row screening; self row is masked
        # (adjacency has no self loops so g[j] enters only via self_value)
        return y.astype(x.dtype)[None]

    def a2a_body(x, adj, bm, k, tt):
        with jax.named_scope("mesh.gather"):
            s = x.reshape(-1)  # [s] (m_loc == 1)
            size = s.shape[0]
            pad = (-size) % m
            sp = jnp.pad(s, (0, pad)).reshape(m, -1)  # [M, chunk]: my coords, split
            if quantize:
                q, scale = _quantize_int8(sp)
                vq = lax.all_to_all(q, node_axes, split_axis=0, concat_axis=0, tiled=True)
                vs = lax.all_gather(scale[None], node_axes, axis=0, tiled=True)  # [M]
                vals = vq.astype(jnp.float32) * screening.per_row(vs, vq)
            else:
                vals = lax.all_to_all(sp, node_axes, split_axis=0, concat_axis=0,
                                      tiled=True)
        # vals[i] = node i's chunk r (r = my node row)
        r = lax.axis_index(node_axes)
        vals = _inject_attack(vals, bm, attack, k, tt, r)
        # Screen chunk r for ALL receivers j.  Sequential over receivers:
        # a vmap here materializes [M, M, chunk] masked copies for the sort
        # (M x the a2a buffer — measured 3.5TB/chip on deepseek-v3), while
        # lax.map keeps the peak at [M, chunk] for identical total compute.
        with jax.named_scope("mesh.screen"):
            y_all = lax.map(
                lambda args: fn(vals, args[0], args[1], b).astype(x.dtype),
                (adj, vals),
            )  # [M, chunk]
        with jax.named_scope("mesh.gather"):
            back = lax.all_to_all(y_all, node_axes, split_axis=0, concat_axis=0,
                                  tiled=True)
        # back[c] = my screened chunk c
        out = back.reshape(-1)[:size]
        return out.reshape(x.shape[1:])[None]

    body = ag_body if schedule == "all_gather" else a2a_body
    shmapped = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec, P(), P(), P(), P()),
        out_specs=spec,
    )
    return shmapped(leaf, adjacency, byz_mask, key, t)


def _node_gram(leaf: jax.Array) -> jax.Array:
    """[M, M] Gram matrix of a [M, ...] leaf — GSPMD reduces over "model"."""
    rest = tuple(range(1, leaf.ndim))
    x = leaf.astype(jnp.float32)
    return jnp.tensordot(x, x, axes=(rest, rest))


def vector_rule_select(
    params: Any,
    *,
    rule: str,
    b: int,
    adjacency: jax.Array,
) -> jax.Array:
    """Compute the per-node selection of BRIDGE-K (index [M]) or BRIDGE-B
    (selection mask [M, M]) from global inter-replica distances."""
    leaves = jax.tree_util.tree_leaves(params)
    gram = functools.reduce(lambda a, c: a + c, [_node_gram(l) for l in leaves])
    sq = jnp.diagonal(gram)
    d2 = jnp.maximum(sq[:, None] + sq[None, :] - 2.0 * gram, 0.0)  # [M, M]
    m = d2.shape[0]
    big = jnp.asarray(1e30, d2.dtype)

    def krum_index(mask_j, j):
        # candidate rows = neighbors; peer distances range over N_j ∪ {j}
        cnt = jnp.sum(mask_j)
        peers = mask_j | (jnp.arange(m) == j)
        dmat = jnp.where(peers[None, :], d2, big)
        dmat = jnp.where(jnp.eye(m, dtype=bool), big, dmat)
        order = jnp.sort(dmat, axis=1)
        kk = jnp.maximum(cnt - b - 2, 1)
        take = jnp.arange(m)[None, :] < kk
        scores = jnp.sum(jnp.where(take, order, 0.0), axis=1)
        scores = jnp.where(mask_j, scores, jnp.inf)
        return jnp.argmin(scores)

    if rule == "krum":
        return jax.vmap(krum_index)(adjacency, jnp.arange(m))

    if rule == "bulyan":
        def select_for(mask_j, j):
            n_sel = jnp.sum(mask_j) - 2 * b
            self_row = jnp.arange(m) == j

            def bodyfn(step, carry):
                cand, sel = carry
                cnt = jnp.sum(cand)
                peers = cand | self_row  # distances range over candidates + self
                dmat = jnp.where(peers[None, :], d2, big)
                dmat = jnp.where(jnp.eye(m, dtype=bool), big, dmat)
                order = jnp.sort(dmat, axis=1)
                kk = jnp.maximum(cnt - b - 2, 1)
                take = jnp.arange(m)[None, :] < kk
                scores = jnp.sum(jnp.where(take, order, 0.0), axis=1)
                scores = jnp.where(cand, scores, jnp.inf)
                i_star = jnp.argmin(scores)
                active = step < n_sel
                pick = jnp.zeros((m,), dtype=bool).at[i_star].set(active)
                return cand & ~pick, sel | pick

            _, sel = lax.fori_loop(0, m, bodyfn, (mask_j, jnp.zeros((m,), bool)))
            return sel

        return jax.vmap(select_for)(adjacency, jnp.arange(m))

    raise ValueError(rule)


def gossip_screen_params(
    params: Any,
    specs: Any,
    *,
    mesh: jax.sharding.Mesh,
    node_axes,
    rule: str,
    b: int,
    adjacency: jax.Array,
    schedule: str = "all_gather",
    byz_mask: jax.Array | None = None,
    attack: str = "none",
    key: jax.Array | None = None,
    t: jax.Array | int = 0,
    quantize: bool = False,
) -> Any:
    """Screen a full [M, ...] parameter pytree.  ``specs`` is a matching pytree
    of PartitionSpecs (node axis first)."""
    if rule in _COORD_RULES:
        return jax.tree_util.tree_map(
            lambda leaf, spec: coordwise_gossip_leaf(
                leaf, spec, mesh=mesh, node_axes=node_axes, rule=rule, b=b,
                adjacency=adjacency, schedule=schedule, byz_mask=byz_mask,
                attack=attack, key=key, t=t, quantize=quantize,
            ),
            params,
            specs,
        )
    if rule == "krum":
        idx = vector_rule_select(params, rule="krum", b=b, adjacency=adjacency)
        return jax.tree_util.tree_map(lambda leaf: jnp.take(leaf, idx, axis=0), params)
    if rule == "bulyan":
        sel = vector_rule_select(params, rule="bulyan", b=b, adjacency=adjacency)

        def leaf_tm(leaf, spec):
            # trimmed mean over the *selected* set (selection mask replaces
            # adjacency); coordinate-wise, so reuse the coordwise machinery.
            return coordwise_gossip_leaf(
                leaf, spec, mesh=mesh, node_axes=node_axes, rule="trimmed_mean",
                b=b, adjacency=sel, schedule=schedule, byz_mask=byz_mask,
                attack=attack, key=key, t=t,
            )

        return jax.tree_util.tree_map(leaf_tm, params, specs)
    raise ValueError(f"unknown rule {rule!r}")
