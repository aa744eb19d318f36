"""Traffic generators: everything a run feeds the program, made from data.

One general generator per kind of input, driven by the parameters of a
traffic file (``bench/traffic/<mix>.json``) and the run's ``--seed``:

* `mnist_like` / `partition_iid` / `node_batches` — the paper task's data
  (a copy of ``repro.data.mnist_like`` and ``repro.data.partition``, kept
  here so that no later change to the program moves the traffic);
* `token_batches` — the LM traffic (a copy of ``repro.data.tokens``'s Markov
  token pipeline, vectorised over the batch);
* `graph` — the cell's communication graph (Watts-Strogatz small world or
  Erdos-Renyi, the recipes of ``repro.core.graph``), fixed by the traffic
  file's own graph seed, never by ``--seed``;
* `byzantine_mask` — which nodes attack, fixed by the traffic file.

All randomness comes from ``numpy.random.default_rng`` on the given seed,
which takes any non-negative integer (run seeds may exceed 32 bits).
"""
from __future__ import annotations

import numpy as np


def seed_u64(seed: int) -> int:
    """Any whole number as a non-negative seed (numpy refuses negatives)."""
    return int(seed) % (1 << 64)


# ---------------------------------------------------------------------------
# the paper task's data
# ---------------------------------------------------------------------------


def _templates(rng: np.random.Generator, n_classes: int) -> np.ndarray:
    yy, xx = np.mgrid[0:28, 0:28] / 28.0
    t = np.zeros((n_classes, 28, 28), np.float32)
    for c in range(n_classes):
        for _ in range(3):
            fx, fy = rng.uniform(0.5, 3.0, 2)
            px, py = rng.uniform(0, 2 * np.pi, 2)
            amp = rng.uniform(0.5, 1.0)
            t[c] += amp * np.sin(2 * np.pi * fx * xx + px) * np.sin(2 * np.pi * fy * yy + py)
        t[c] = (t[c] - t[c].min()) / (t[c].max() - t[c].min() + 1e-9)
    return t


def mnist_like(num: int, *, n_classes: int, noise: float, seed: int):
    """``num`` 28x28 images of ``n_classes`` smooth class templates, shifted by
    up to 2 pixels, plus Gaussian noise; standardised.  Returns
    ``(x [num, 784] f32, y [num] i32)``."""
    rng = np.random.default_rng(seed_u64(seed))
    templates = _templates(rng, n_classes)
    y = rng.integers(0, n_classes, num).astype(np.int32)
    x = templates[y].copy()
    shifts = rng.integers(-2, 3, (num, 2))
    for i in range(num):
        x[i] = np.roll(np.roll(x[i], shifts[i, 0], axis=0), shifts[i, 1], axis=1)
    x += noise * rng.standard_normal(x.shape).astype(np.float32)
    x = x.reshape(num, 784).astype(np.float32)
    return (x - x.mean()) / (x.std() + 1e-6), y


def partition_iid(x, y, num_nodes: int, *, seed: int):
    idx = np.random.default_rng(seed_u64(seed)).permutation(len(x))
    return [(x[s], y[s]) for s in np.array_split(idx, num_nodes)]


def node_batches(shards, batch: int, ticks: int, *, seed: int):
    """``ticks`` minibatches ``(x [M, batch, 784], y [M, batch])``, each node
    drawing ``batch`` samples with replacement from its own shard."""
    rng = np.random.default_rng(seed_u64(seed))
    out = []
    for _ in range(ticks):
        idx = [rng.integers(0, len(xj), batch) for xj, _ in shards]
        out.append((np.stack([xj[i] for (xj, _), i in zip(shards, idx)]),
                    np.stack([yj[i] for (_, yj), i in zip(shards, idx)])))
    return out


# ---------------------------------------------------------------------------
# LM traffic
# ---------------------------------------------------------------------------


def token_batches(*, vocab: int, nodes: int, batch: int, seq: int, ticks: int,
                  successors: int, restart: float, seed: int):
    """``ticks`` batches ``{"tokens": [nodes, batch, seq + 1] i32}`` of a sparse
    Markov chain over the whole vocabulary: every token has ``successors``
    likely next tokens, and a share ``restart`` of positions restart at a
    uniform token (so the loss can fall, as on text)."""
    rng = np.random.default_rng(seed_u64(seed))
    succ = rng.integers(0, vocab, (vocab, successors))
    out = []
    for _ in range(ticks):
        toks = np.empty((nodes, batch, seq + 1), np.int32)
        cur = rng.integers(0, vocab, (nodes, batch))
        toks[..., 0] = cur
        choice = rng.integers(0, successors, (seq, nodes, batch))
        restarts = rng.random((seq, nodes, batch)) < restart
        fresh = rng.integers(0, vocab, (seq, nodes, batch))
        for t in range(seq):
            cur = np.where(restarts[t], fresh[t], succ[cur, choice[t]])
            toks[..., t + 1] = cur
        out.append({"tokens": toks})
    return out


# ---------------------------------------------------------------------------
# the deployment: graph and Byzantine set
# ---------------------------------------------------------------------------


def small_world(m: int, nearest: int, b: int, *, rewire: float, max_degree: int,
                seed: int) -> np.ndarray:
    """Watts-Strogatz ring lattice (``nearest`` on each side), each edge's far
    end rewired with probability ``rewire`` while every degree stays in
    ``[2b + 1, max_degree]``.  ``adj[j, i]``: node j receives from node i."""
    need = 2 * b + 1
    rng = np.random.default_rng(seed_u64(seed))
    adj = np.zeros((m, m), dtype=bool)
    for j in range(m):
        for off in range(1, nearest + 1):
            adj[j, (j + off) % m] = True
    adj = adj | adj.T
    deg = adj.sum(axis=1)
    for j in range(m):
        for off in range(1, nearest + 1):
            if rng.random() < rewire:
                tgt = (j + off) % m
                cand = int(rng.integers(m))
                if (cand != j and not adj[j, cand] and adj[j, tgt]
                        and deg[tgt] > need and deg[cand] < max_degree
                        and deg[j] <= max_degree):
                    adj[j, tgt] = adj[tgt, j] = False
                    adj[j, cand] = adj[cand, j] = True
                    deg[tgt] -= 1
                    deg[cand] += 1
    np.fill_diagonal(adj, False)
    return adj


def erdos_renyi(m: int, p: float, b: int, *, seed: int, tries: int = 200) -> np.ndarray:
    """Symmetric G(m, p) redrawn until every degree exceeds 2b."""
    rng = np.random.default_rng(seed_u64(seed))
    for _ in range(tries):
        adj = np.triu(rng.random((m, m)) < p, 1)
        adj = adj | adj.T
        np.fill_diagonal(adj, False)
        if adj.sum(axis=1).min() > 2 * b:
            return adj
    raise ValueError(f"no G({m}, {p}) with every degree > {2 * b} in {tries} draws")


def graph(spec: dict, m: int, b: int) -> np.ndarray:
    if spec["kind"] == "small_world":
        return small_world(m, spec["nearest"], b, rewire=spec["rewire"],
                           max_degree=spec["max_degree"], seed=spec["seed"])
    if spec["kind"] == "erdos_renyi":
        return erdos_renyi(m, spec["p"], b, seed=spec["seed"])
    raise ValueError(f"unknown graph kind {spec['kind']!r}")


def byzantine_mask(m: int, count: int, seed: int) -> np.ndarray:
    """``count`` distinct attackers drawn uniformly with a fixed seed."""
    mask = np.zeros((m,), dtype=bool)
    if count:
        mask[np.random.default_rng(seed_u64(seed)).choice(m, size=count, replace=False)] = True
    return mask
