"""Plain reference of one BRIDGE tick (Algorithm 1 of arXiv:1908.08098).

For every node j, at iterate w_j(t) and step size rho(t) = 1 / (lam (t0 + t)):

1. g_j = grad f_j(w_j(t)) on node j's batch;
2. every node broadcasts w_j(t); a Byzantine node broadcasts the attack's
   value instead;
3. node j takes, coordinate by coordinate, the values broadcast by its
   in-neighbours, drops the b largest and the b smallest, and averages the
   rest together with its own broadcast value (BRIDGE-T, Eq. 7-10);
4. w_j(t + 1) = y_j - rho(t) g_j.

Nothing here comes from the program: the graph and the Byzantine set are the
traffic file's, the weights the benchmark's own (`bench.weights`).  Steps
2-4 run on column blocks of each leaf so that a 198M-parameter node fits
beside its gradients; they are coordinate-wise, so blocks change nothing.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 1 << 22  # coordinates per column block of steps 2-4


def attack(spec: dict, w, byz):
    """Broadcast values: honest rows are w; Byzantine rows per ``spec``."""
    name = spec["name"]
    if name == "none":
        return w
    if name == "sign_flip":
        return jnp.where(byz[:, None], -spec["scale"] * w, w)
    if name == "alie":  # mean + z * std of the honest values, per coordinate
        hw = w[~byz]
        mu = jnp.mean(hw, axis=0)
        sd = jnp.sqrt(jnp.mean((hw - mu) ** 2, axis=0) + 1e-12)
        return jnp.where(byz[:, None], (mu + spec["z"] * sd)[None, :], w)
    raise ValueError(f"unknown attack {name!r}")


def degree_groups(adj: np.ndarray):
    """[(nodes [n], in-neighbours [n, k])] for each in-degree k in the graph."""
    deg = adj.sum(axis=1)
    return [(np.nonzero(deg == k)[0],
             np.stack([np.nonzero(adj[j])[0] for j in np.nonzero(deg == k)[0]]))
            for k in sorted(set(deg.tolist()))]


def trimmed_mean(wb, groups, b: int):
    y = jnp.zeros_like(wb)
    for nodes, nbrs in groups:
        k = nbrs.shape[1]
        kept = jnp.sort(wb[nbrs], axis=1)[:, b:k - b]  # [n, k - 2b, c]
        y = y.at[nodes].set((jnp.sum(kept, axis=1) + wb[nodes]) / (k - 2 * b + 1))
    return y


def block_step(*, adj, byz, b, attack_spec, dtype, exchange=True):
    """Steps 2-4 as one jitted function of a column block ``(x [M, c],
    g [M, c], rho)``.  ``exchange=False`` leaves the exchange out (each node
    keeps its own value): a fault that the comparison must catch, never a
    sound run."""
    groups = degree_groups(adj)
    byz = np.asarray(byz, bool)

    def step(x, g, rho):
        x, g = x.astype(dtype), g.astype(dtype)
        wb = attack(attack_spec, x, byz)
        y = trimmed_mean(wb, groups, b) if exchange else wb
        return y - rho.astype(dtype) * g

    return jax.jit(step)


def screen_and_apply(fn, x, g, rho):
    """`block_step` ``fn`` over one leaf's ``[M, n]`` iterates and gradients,
    a column block at a time (the last block padded to the block's width,
    so that one program serves every block)."""
    n = x.shape[1]
    rho = jnp.asarray(rho, jnp.float32)
    if n <= BLOCK:
        return fn(x, g, rho)
    out = []
    for i in range(0, n, BLOCK):
        xb, gb = x[:, i:i + BLOCK], g[:, i:i + BLOCK]
        pad = BLOCK - xb.shape[1]
        if pad:
            xb, gb = jnp.pad(xb, ((0, 0), (0, pad))), jnp.pad(gb, ((0, 0), (0, pad)))
        out.append(fn(xb, gb, rho)[:, :BLOCK - pad])
    return jnp.concatenate(out, axis=1)


def run(params, batches, *, node_grad, adj, byz, b, attack_spec, lam, t0,
        honest, nodes_per_call, dtype=jnp.float32, exchange=True):
    """Follow ``len(batches)`` ticks from ``params`` ([M, ...] pytree).

    Gradients are taken ``nodes_per_call`` nodes at a time (1 for a model
    that fills the chip).  Returns ``(params_after, losses [T],
    first_grad_norms {leaf: norm})``: the per-tick loss is the mean over the
    ``honest`` nodes, and the gradient norms are of tick 0's gradients of the
    honest nodes, per leaf."""
    m = jax.tree_util.tree_leaves(params)[0].shape[0]
    grad_many = jax.jit(jax.vmap(node_grad))
    step = block_step(adj=adj, byz=byz, b=b, attack_spec=attack_spec, dtype=dtype,
                      exchange=exchange)
    take = lambda tree, lo: jax.tree_util.tree_map(lambda a: a[lo:lo + nodes_per_call], tree)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    del params
    losses, first_norms = [], None
    with jax.default_matmul_precision("highest"):
        for t, batch in enumerate(batches):
            cur = jax.tree_util.tree_unflatten(treedef, leaves)
            parts, node_losses = [], []
            for lo in range(0, m, nodes_per_call):
                loss, g = grad_many(take(cur, lo), take(batch, lo))
                node_losses.append(np.asarray(loss, np.float64))
                parts.append(jax.tree_util.tree_leaves(g))
            del cur, g
            losses.append(float(np.mean(np.concatenate(node_losses)[honest])))
            rho = 1.0 / (lam * (t0 + t))
            # leaf by leaf, so that one leaf's gradients and old values are
            # freed before the next leaf's are gathered
            norms = []
            for i, x in enumerate(leaves):
                gi = jnp.concatenate([p[i] for p in parts])
                for p in parts:
                    p[i] = None
                if first_norms is None:
                    norms.append(float(jnp.sqrt(jnp.sum(jnp.square(
                        gi[np.nonzero(honest)[0]].astype(jnp.float32))))))
                leaves[i] = screen_and_apply(step, x.reshape(m, -1), gi.reshape(m, -1),
                                             rho).reshape(x.shape)
                del x, gi
            if first_norms is None:
                paths = [jax.tree_util.keystr(k) for k, _ in
                         jax.tree_util.tree_flatten_with_path(
                             jax.tree_util.tree_unflatten(treedef, leaves))[0]]
                first_norms = dict(zip(paths, norms, strict=True))
    return jax.tree_util.tree_unflatten(treedef, leaves), np.asarray(losses), first_norms


def change_norms(after, before, rows) -> dict:
    """{leaf path: norm of (after - before) over the nodes ``rows``
    selects}, one leaf at a time on the device (``after`` may be on the
    host)."""
    idx = np.nonzero(rows)[0]
    out = {}
    for (k, a), b_ in zip(jax.tree_util.tree_flatten_with_path(after)[0],
                          jax.tree_util.tree_leaves(before), strict=True):
        d = jnp.asarray(np.asarray(a)[idx], jnp.float32) - jnp.asarray(b_)[idx].astype(jnp.float32)
        out[jax.tree_util.keystr(k)] = float(jnp.sqrt(jnp.sum(jnp.square(d))))
    return out
