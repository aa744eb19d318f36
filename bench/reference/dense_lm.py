"""Plain reference of a dense decoder-only LM with the Qwen3 block
(arXiv:2505.09388; the model's published config.json): pre-RMSNorm, GQA
attention with RMSNorm on q and k per head, rotary embeddings (rotate-half
layout), SwiGLU MLP, final RMSNorm, linear head; next-token cross-entropy
averaged over every position.

Written from the published description with no kernel, chunking or cache:
full [S, S] causal scores, materialised.  Two points follow the system under
test rather than the published model, and are named in the configuration
file's ``departures``:

* the token embedding is multiplied by sqrt(hidden_size) before the first
  block (Qwen3 does not scale it);
* every RMSNorm weight is stored as (weight - 1), so a stored zero is the
  published weight 1; this is a change of parametrisation only.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

EPS_KEY = "rms_norm_eps"


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + w)


def _rope(x, theta):
    """x [B, S, H, hd]: rotate the two halves of each head by position."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]  # [S, hd/2]
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def logits(p, tokens, cfg, dtype=jnp.float32):
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps = cfg[EPS_KEY]
    mm = lambda a, b: jnp.matmul(a, b, precision="highest")
    c = lambda a: a.astype(dtype)
    x = c(p["embed"])[tokens] * jnp.asarray(d ** 0.5, dtype)
    b_, s = tokens.shape
    causal = jnp.tril(jnp.ones((s, s), bool))
    for layer in range(cfg["num_hidden_layers"]):
        blk = jax.tree_util.tree_map(lambda a: c(a[layer, 0]), p["blocks"])
        at = blk["attn"]
        hx = _rms(x, blk["ln1"]["w"], eps)
        q = _rms(mm(hx, at["wq"]).reshape(b_, s, h, hd), at["q_norm"]["w"], eps)
        k = _rms(mm(hx, at["wk"]).reshape(b_, s, kv, hd), at["k_norm"]["w"], eps)
        v = mm(hx, at["wv"]).reshape(b_, s, kv, hd)
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
        k = jnp.repeat(k, h // kv, axis=2)
        v = jnp.repeat(v, h // kv, axis=2)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") / jnp.asarray(hd ** 0.5, dtype)
        sc = jnp.where(causal[None, None], sc, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v, precision="highest")
        x = x + mm(o.reshape(b_, s, h * hd), at["wo"])
        hx = _rms(x, blk["ln2"]["w"], eps)
        ml = blk["mlp"]
        x = x + mm(jax.nn.silu(mm(hx, ml["wg"])) * mm(hx, ml["wu"]), ml["wd"])
    x = _rms(x, c(p["ln_f"]["w"]), eps)
    return mm(x, c(p["head"]))


def loss(p, batch, cfg, dtype=jnp.float32):
    toks = jnp.asarray(batch["tokens"])
    lg = logits(p, toks[:, :-1], cfg, dtype)
    lse = jax.nn.logsumexp(lg, axis=-1)
    ll = jnp.take_along_axis(lg, toks[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(lse - ll)


def node_grad(cfg, dtype=jnp.float32):
    return jax.value_and_grad(lambda p, bt: loss(p, bt, cfg, dtype))
