"""repro.comm: compressed Byzantine-resilient exchange.

The subsystem's contract surface (ISSUE 3 acceptance):
* codec round-trip properties — identity is an exact bitcast (including
  ``-0.0``), stochastic quantizers are mean-preserving and step-bounded,
  sparsifiers keep exactly k coordinates;
* exact bits-on-wire accounting (int8+top-k >= 4x under paper-scale d);
* banked ``lax.switch`` dispatch == dedicated codec, bit-for-bit;
* error-feedback residuals stay bounded and compressed BRIDGE converges
  next to the uncompressed trainer;
* identity-codec runs are bit-identical to the uncompressed
  `BridgeTrainer` / `GridEngine`, and a codec x rule x attack grid still
  compiles ONCE;
* compressed-domain attacks (garbage codewords, quant-scale abuse, sparse
  index lies) are decoded and *screened*;
* `repro.net` charges serialization latency from ``wire_bits`` and samples
  bandwidth-cap survivors from the per-tick PRNG (regression: the old
  deterministic prefix mask starved high-index coordinates);
* fused Pallas dequant->screen kernels == decode-then-screen references;
* `benchmarks.check_regression` per-file re-baselining + missing-baseline
  warn-not-fail.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm import CommState, codec_bank, decode_bank, encode_bank, get_codec, wire_bits_bank
from repro.core import BridgeConfig, BridgeTrainer, erdos_renyi, replicate
from repro.core import byzantine as byz_lib
from repro.net import AsyncBridgeConfig, AsyncBridgeTrainer, ChannelConfig, UnreliableRuntime
from repro.sim import ExperimentGrid, GridEngine
from repro.sim.engine import stack_batches

M, D, T = 12, 5, 20


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


# ---------------------------------------------------------------------------
# Codec round-trip properties
# ---------------------------------------------------------------------------


def test_identity_codec_is_exact_bitcast():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(7, 130)), jnp.float32)
    x = x.at[0, 0].set(-0.0).at[1, 1].set(jnp.inf)  # bit-level corner cases
    c = get_codec("identity")
    out = c.decode(c.encode(jax.random.PRNGKey(0), x), 130)
    np.testing.assert_array_equal(
        np.asarray(x).view(np.uint32), np.asarray(out).view(np.uint32))
    assert c.lossless and c.wire_bits(130) == 32 * 130


@pytest.mark.parametrize("name,levels", [("int8", 127), ("int4", 7)])
def test_quantizer_step_bound_and_unbiasedness(name, levels):
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(4, 96)), jnp.float32)
    c = get_codec(name)
    step = np.asarray(jnp.max(jnp.abs(x), axis=-1, keepdims=True)) / levels
    # per-draw error never exceeds one quantization step
    out = c.decode(c.encode(jax.random.PRNGKey(0), x), 96)
    assert np.all(np.abs(np.asarray(out - x)) <= step + 1e-6)
    # stochastic rounding is mean-preserving: the average over keys
    # approaches x much closer than any deterministic rounding bias could
    outs = jnp.stack([
        c.decode(c.encode(jax.random.PRNGKey(i), x), 96) for i in range(400)
    ])
    bias = np.abs(np.asarray(outs.mean(0) - x))
    assert np.max(bias / step) < 0.25


@pytest.mark.parametrize("name", ["topk25", "randk25", "topk25_int8"])
def test_sparse_codecs_keep_exactly_k(name):
    rng = np.random.default_rng(2)
    d = 120
    x = jnp.asarray(rng.normal(size=(3, d)), jnp.float32)
    c = get_codec(name)
    k = c.kept(d)
    assert k == 30
    out = np.asarray(c.decode(c.encode(jax.random.PRNGKey(0), x), d))
    assert (np.count_nonzero(out, axis=-1) <= k).all()
    if name == "topk25":
        # exact top-|x| selection survives the float32 round trip
        for row_out, row_in in zip(out, np.asarray(x), strict=True):
            kept = np.nonzero(row_out)[0]
            top = np.argsort(-np.abs(row_in))[:k]
            assert set(kept) == set(top)
            np.testing.assert_array_equal(row_out[kept], row_in[kept])


def test_wire_bits_exact_accounting():
    import math

    d = 7850  # the MNIST-like linear model's flattened dimension
    nsc = -(-d // 128)  # one 32-bit dequant scale per SCALE_BLOCK=128 coords
    ident = get_codec("identity").wire_bits(d)
    assert ident == 32 * d
    assert get_codec("int8").wire_bits(d) == 8 * d + 32 * nsc
    assert get_codec("int4").wire_bits(d) == 4 * d + 32 * nsc
    # randk ships no indices (shared PRNG); topk ships its k-subset as an
    # enumerative (combinatorial number system) rank: ceil(log2 C(d, k))
    k = get_codec("randk25").kept(d)
    assert get_codec("randk25").wire_bits(d) == 32 * k
    rank_bits = (math.comb(d, k) - 1).bit_length()
    assert get_codec("topk25").wire_bits(d) == 32 * k + rank_bits
    assert get_codec("topk25_int8").wire_bits(d) == 8 * k + rank_bits + 32 * (-(-k // 128))
    # the acceptance codec: int8 values + top-half-k indices >= 4x smaller
    # while dense enough for loss parity (benchmarks/comm_bench.py)
    assert ident / get_codec("topk50_int8").wire_bits(d) >= 4.0
    assert ident / get_codec("topk25_int8").wire_bits(d) >= 4.0


def test_codec_registry_validation():
    with pytest.raises(ValueError, match="unknown codec"):
        get_codec("zstd")
    with pytest.raises(ValueError, match="codecs"):
        ExperimentGrid(erdos_renyi(M, 0.8, 1, seed=0), ("trimmed_mean",), ("random",),
                       codecs=("identity", "identity"))
    with pytest.raises(ValueError, match="unknown codec"):
        ExperimentGrid(erdos_renyi(M, 0.8, 1, seed=0), ("trimmed_mean",), ("random",),
                       codecs=("gzip",))


def test_banked_dispatch_matches_dedicated_codec():
    rng = np.random.default_rng(3)
    d = 130
    x = jnp.asarray(rng.normal(size=(6, d)), jnp.float32)
    st0 = CommState(est=jnp.zeros_like(x), resid=jnp.zeros_like(x))
    names = ("identity", "int8", "topk25_int8")
    bank = codec_bank(names)
    key = jax.random.PRNGKey(7)
    for i, name in enumerate(names):
        # zero estimate + zero residual: the transmitted delta is x itself,
        # so the banked round trip must equal the dedicated codec's
        msg, tgt = encode_bank(bank, jnp.int32(i), key, x, st0)
        x_hat, st1 = decode_bank(bank, jnp.int32(i), msg, tgt, st0)
        ded = get_codec(name)
        expect = ded.decode(ded.encode(key, x), d)
        np.testing.assert_array_equal(np.asarray(x_hat), np.asarray(expect))
        assert int(wire_bits_bank(bank, jnp.int32(i), d)) == ded.wire_bits(d)
        # the public copy moved to what receivers decoded
        np.testing.assert_array_equal(np.asarray(st1.est if name != "identity" else x_hat),
                                      np.asarray(x_hat))


# ---------------------------------------------------------------------------
# Error feedback: bounded residual, convergence next to uncompressed
# ---------------------------------------------------------------------------


def _run_trainer(topo, targets, codec, steps=150, attack="random", rule="trimmed_mean"):
    cfg = BridgeConfig(topology=topo, rule=rule, num_byzantine=2, attack=attack,
                       codec=codec, lam=1.0, t0=10)
    tr = BridgeTrainer(cfg, quad_grad_fn)
    st = tr.init(init_fn(0))
    norms = []
    for _ in range(steps):
        st, m = tr.step(st, targets)
        norms.append(float(m["ef_residual_norm"]))
    return tr, st, m, norms


def test_error_feedback_residual_bounded_and_convergent(topo, targets):
    _, _, m_id, norms_id = _run_trainer(topo, targets, "identity")
    assert norms_id == [0.0] * len(norms_id)  # lossless: no feedback at all
    for codec in ("int8", "int4"):
        tr, st, m, norms = _run_trainer(topo, targets, codec)
        # the residual is the compressor's bounded steady-state error, not a
        # divergent accumulator: its tail never exceeds a few times its
        # early levels and stays finite
        assert np.isfinite(norms).all()
        assert max(norms[-30:]) <= 5.0 * max(max(norms[:30]), 1e-3)
        # compressed BRIDGE lands next to the uncompressed trainer
        assert float(m["loss"]) < float(m_id["loss"]) * 1.10 + 0.05
        assert float(m["consensus_dist"]) < 0.5


def test_topk_with_error_feedback_converges(topo, targets):
    _, _, m_id, _ = _run_trainer(topo, targets, "identity", steps=250)
    _, _, m, norms = _run_trainer(topo, targets, "topk25_int8", steps=250)
    assert np.isfinite(norms).all()
    assert float(m["loss"]) < float(m_id["loss"]) * 1.15 + 0.1
    assert float(m["consensus_dist"]) < 0.5


# ---------------------------------------------------------------------------
# Identity bit-equivalence + one-compile codec grids
# ---------------------------------------------------------------------------


def _sequential(topo, targets, cell):
    # the cell's mask_seed (seed-axis-varying since ISSUE 4) maps onto the
    # trainer's byzantine_seed — same draw, same attacking nodes
    cfg = BridgeConfig(topology=topo, rule=cell.rule, num_byzantine=cell.b,
                       attack=cell.attack, codec=cell.codec, lam=1.0, t0=10.0,
                       byzantine_seed=cell.mask_seed if cell.mask_seed is not None else 0)
    tr = BridgeTrainer(cfg, quad_grad_fn)
    st = tr.init(init_fn(cell.seed), seed=cell.seed)
    losses = []
    for _ in range(T):
        st, m = tr.step(st, targets)
        losses.append(m["loss"])
    return np.asarray(st.params["w"]), np.asarray(jnp.stack(losses))


@pytest.mark.slow
def test_codec_grid_compiles_once_and_matches_trainers(topo, targets, batches):
    """codec x rule x attack x seed as ONE compiled program, every cell
    bit-identical to its own (codec-configured) BridgeTrainer run."""
    grid = ExperimentGrid(topo, ("trimmed_mean", "median"), ("random", "scale_abuse"),
                          (2,), (0, 1), codecs=("identity", "int8", "topk25_int8"),
                          lam=1.0, t0=10.0)
    engine = GridEngine(grid, quad_grad_fn)
    state = engine.init(init_fn)
    assert engine.trace_count == 0
    final, metrics = engine.run(state, batches)
    assert engine.trace_count == 1  # 24 experiments, one compilation
    assert engine.num_cells == 24
    for i, cell in enumerate(engine.cells):
        w_seq, loss_seq = _sequential(topo, targets, cell)
        np.testing.assert_array_equal(w_seq, np.asarray(final.params["w"][i]),
                                      err_msg=f"params diverged for {cell}")
        np.testing.assert_array_equal(loss_seq, np.asarray(metrics["loss"][i]),
                                      err_msg=f"loss trace diverged for {cell}")
    # per-cell wire accounting is the codec's exact constant
    for i, cell in enumerate(engine.cells):
        assert float(metrics["wire_bits_per_edge"][i, -1]) == get_codec(cell.codec).wire_bits(D)


def test_banked_codec_grid_identity_cells_exact_lossy_allclose(topo, targets, batches):
    """group=False (fully banked switches): identity cells stay bit-exact;
    lossy codecs agree to ULP (XLA's FMA contraction of the dequant multiply
    is program-shape dependent — see repro.comm.exchange)."""
    grid = ExperimentGrid(topo, ("trimmed_mean",), ("random",), (2,), (0, 1),
                          codecs=("identity", "int8"), lam=1.0, t0=10.0)
    grouped = GridEngine(grid, quad_grad_fn)
    banked = GridEngine(grid, quad_grad_fn, group=False)
    f1, _ = grouped.run(grouped.init(init_fn), batches)
    f2, _ = banked.run(banked.init(init_fn), batches)
    for i, cell in enumerate(grouped.cells):
        a, b = np.asarray(f1.params["w"][i]), np.asarray(f2.params["w"][i])
        if cell.codec == "identity":
            np.testing.assert_array_equal(a, b, err_msg=f"{cell}")
        else:
            # the 1-ULP/step contraction drift compounds through the tracked
            # estimate; after T=20 ticks it sits ~1e-4, far below the int8
            # quantization step (~1e-2) that bounds the codec's real error
            np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-3, err_msg=f"{cell}")


def test_identity_codec_async_still_bitwise_equals_sync(topo, targets):
    """The comm plumbing is transparent end-to-end: the ideal-channel async
    path (which now encodes/decodes per link) still reproduces the
    synchronous trainer bit-for-bit under the identity codec."""
    cfg = BridgeConfig(topology=topo, rule="trimmed_mean", num_byzantine=2,
                       attack="random", lam=1.0, t0=10)
    sync = BridgeTrainer(cfg, quad_grad_fn)
    acfg = AsyncBridgeConfig(topology=topo, rule="trimmed_mean", num_byzantine=2,
                             attack="random", lam=1.0, t0=10,
                             channel=ChannelConfig.ideal(), staleness_bound=0)
    atr = AsyncBridgeTrainer(acfg, quad_grad_fn)
    s1, s2 = sync.init(init_fn(0)), atr.init(init_fn(0))
    for _ in range(25):
        s1, _ = sync.step(s1, targets)
        s2, _ = atr.step(s2, targets)
    np.testing.assert_array_equal(np.asarray(s1.params["w"]), np.asarray(s2.params["w"]))


# ---------------------------------------------------------------------------
# Compressed-domain attacks: screening sees what decoders emit
# ---------------------------------------------------------------------------


def test_wire_attack_registry():
    assert set(byz_lib.WIRE_ATTACKS) >= {"none", "garbage_codeword", "scale_abuse", "index_lie"}
    # wire attacks resolve to the no-op in the iterate-domain registries
    assert byz_lib.get_attack("scale_abuse").name == "none"
    assert byz_lib.get_message_attack("garbage_codeword").name == "none"
    for n in ("garbage_codeword", "scale_abuse", "index_lie"):
        assert n in byz_lib.attack_names()
    bank = byz_lib.wire_attack_bank(("random", "scale_abuse"))
    assert [a.name for a in bank] == ["none", "scale_abuse"]


def test_scale_abuse_decodes_huge_but_is_screened(topo, targets):
    """Quant-range abuse inflates Byzantine codewords by 1e4 — screening
    still trims them: honest nodes converge near the honest mean."""
    tr, st, m, _ = _run_trainer(topo, targets, "int8", steps=250, attack="scale_abuse")
    hm = np.asarray(tr.honest_mask)
    t = np.asarray(targets)[hm]
    w_fin = np.asarray(st.params["w"])[hm].mean(0)
    assert np.isfinite(np.asarray(st.params["w"])).all()
    assert np.linalg.norm(w_fin - t.mean(0)) < 1.0
    assert float(m["consensus_dist"]) < 0.5


def test_garbage_codeword_survives_identity_decode(topo, targets):
    """Garbage payload bytes under the identity codec decode to arbitrary
    float bit patterns (inf/NaN included); the NaN guard + inf sentinels keep
    screening finite and convergent."""
    tr, st, m, _ = _run_trainer(topo, targets, "identity", steps=250,
                                attack="garbage_codeword")
    assert np.isfinite(np.asarray(st.params["w"])).all()
    assert float(m["consensus_dist"]) < 0.5


def test_randk_rederives_indices_index_lies_cannot_bite():
    """randk's wire format ships ZERO index bits — receivers re-derive the
    subset from the shared per-tick PRNG — so a forged idx field must change
    nothing when the decoder holds the key (the in-protocol path)."""
    rng = np.random.default_rng(6)
    d = 64
    x = jnp.asarray(rng.normal(size=(4, d)), jnp.float32)
    byz = jnp.asarray([False, True, False, False])
    key = jax.random.PRNGKey(3)
    c = get_codec("randk25")
    msg = c.encode(key, x)
    lied = byz_lib.WIRE_ATTACKS["index_lie"](msg, byz, key, jnp.int32(0), d)
    np.testing.assert_array_equal(np.asarray(c.decode(msg, d, key)),
                                  np.asarray(c.decode(lied, d, key)))
    # and the re-derived decode round-trips exactly like the carried-idx one
    np.testing.assert_array_equal(np.asarray(c.decode(msg, d, key)),
                                  np.asarray(c.decode(msg, d)))


def test_index_lie_only_bites_sparse_codecs():
    rng = np.random.default_rng(5)
    d = 64
    x = jnp.asarray(rng.normal(size=(4, d)), jnp.float32)
    byz = jnp.asarray([False, True, False, False])
    key = jax.random.PRNGKey(0)
    atk = byz_lib.WIRE_ATTACKS["index_lie"]
    for name, bites in (("int8", False), ("topk25", True)):
        c = get_codec(name)
        msg = c.encode(key, x)
        attacked = atk(msg, byz, key, jnp.int32(0), d)
        clean = np.asarray(c.decode(msg, d))
        lied = np.asarray(c.decode(attacked, d))
        np.testing.assert_array_equal(clean[~np.asarray(byz)], lied[~np.asarray(byz)])
        changed = not np.array_equal(clean[1], lied[1])
        assert changed == bites
        if bites:  # all adversarial energy lands on the first k coordinates
            assert (np.nonzero(lied[1])[0] < c.kept(d)).all()


# ---------------------------------------------------------------------------
# repro.net: serialization from wire_bits + PRNG bandwidth masking
# ---------------------------------------------------------------------------


def test_serialization_ticks_from_wire_bits():
    ch = ChannelConfig(bits_per_tick=1000)
    assert ch.serial_ticks(900) == 0  # fits in the send tick
    assert ch.serial_ticks(1001) == 1
    assert ch.serial_ticks(5000) == 4
    assert int(ch.serial_ticks(jnp.int32(5000))) == 4
    assert ChannelConfig().serial_ticks(10**6) == 0  # uncapped link
    assert ch.max_total_latency(5000) == 4
    d = 100
    ident, int8 = get_codec("identity").wire_bits(d), get_codec("int8").wire_bits(d)
    assert ch.serial_ticks(ident) > ch.serial_ticks(int8)  # compression buys ticks


def test_narrowband_delivery_codec_dependent(topo, targets):
    """On a serialization-limited link the float32 payload arrives ticks
    later than the int8 codeword — delivered_frac at tick 0 shows it."""
    d = 100
    ch = ChannelConfig(bits_per_tick=get_codec("int8").wire_bits(d) + 1)
    rt = UnreliableRuntime(topo, ch, staleness_bound=10)
    m = topo.num_nodes
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(m, d)), jnp.float32)
    msgs = jnp.broadcast_to(w[None], (m, m, d))
    adj = jnp.asarray(topo.adjacency)
    key = jax.random.PRNGKey(0)
    for codec, frac in (("int8", 1.0), ("identity", 0.0)):
        wb = get_codec(codec).wire_bits(d)
        net = rt.init(m, d, max_wire_bits=get_codec("identity").wire_bits(d))
        net, _, _, stats = rt.exchange(net, msgs, w, adj, key, jnp.int32(0), wire_bits=wb)
        assert float(stats["delivered_frac"]) == frac


def test_bandwidth_cap_subset_fixed_at_send_time(topo):
    """The transmitted coordinate subset is part of the in-flight message:
    re-reading a stale mailbox entry on later ticks must NOT re-draw the
    mask and leak coordinates that never crossed the wire."""
    d = 10
    ch = ChannelConfig(bandwidth_cap=3, latency_min=1, latency_max=1)
    rt = UnreliableRuntime(topo, ch, staleness_bound=10)
    m = topo.num_nodes
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(m, d)), jnp.float32)
    msgs = jnp.broadcast_to(w[None], (m, m, d))
    adj = jnp.asarray(topo.adjacency)
    none = jnp.zeros_like(adj)
    net = rt.init(m, d)
    net, v0, _, _ = rt.exchange(net, msgs, w, adj, jax.random.PRNGKey(0), jnp.int32(0))
    # tick 1 delivers; ticks 2..4 read the SAME stored entry with no new sends
    views = []
    for t in range(1, 5):
        net, v, mask, _ = rt.exchange(net, msgs, w, none, jax.random.PRNGKey(t), jnp.int32(t))
        views.append(np.asarray(v))
    j, i = map(int, np.argwhere(np.asarray(adj))[0])
    sent = ~np.isclose(views[0][j, i], np.asarray(w)[j])  # coords from the sender
    assert sent.sum() <= 3
    for v in views[1:]:
        np.testing.assert_array_equal(views[0][j, i], v[j, i],
                                      err_msg="stale entry changed across reads (mask leak)")


def test_bandwidth_cap_prefix_bias_regression():
    """The old mask transmitted the FIRST `cap` coordinates every tick — a
    deterministic prefix that permanently starved high-index coordinates.
    The per-tick PRNG subset covers every coordinate with roughly uniform
    frequency (and still transmits exactly `cap` of them)."""
    d, cap, ticks = 32, 8, 300
    ch = ChannelConfig(bandwidth_cap=cap)
    counts = np.zeros(d)
    for i in range(ticks):
        mask = np.asarray(ch.coord_mask(jax.random.PRNGKey(i), d))
        assert mask.sum() == cap
        counts += mask
    assert counts.min() > 0, "some coordinate never transmitted (prefix bias)"
    # uniform-ish coverage: every coordinate within 3x of the expected rate
    expected = ticks * cap / d
    assert counts.max() < 3 * expected and counts.min() > expected / 3
    # and the old deterministic-prefix behaviour is really gone: the tail
    # (coords >= cap) transmits about as often as the head
    assert counts[cap:].sum() > 0.5 * counts.sum()


# ---------------------------------------------------------------------------
# Fused Pallas dequant->screen kernels
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("shape,b", [((9, 130), 1), ((16, 700), 3), ((3, 9, 130), 2)])
def test_fused_dequant_trimmed_mean_matches_reference(shape, b):
    from repro.kernels import ops, ref

    rng = np.random.default_rng(shape[-1] + b)
    x = jnp.asarray(rng.normal(size=shape), jnp.float32)
    msg = get_codec("int8").encode(jax.random.PRNGKey(0), x)
    lead, d = shape[:-1], shape[-1]
    mask = jnp.asarray(rng.random(lead) < 0.8)
    mask = mask.at[..., : 2 * b + 1].set(True)
    sv = jnp.asarray(rng.normal(size=shape[:-2] + (d,)), jnp.float32)
    out = ops.dequant_trimmed_mean(msg.payload, msg.scale, mask, sv, b, block_d=128, interpret=True)
    exp = ref.dequant_trimmed_mean_ref(msg.payload, msg.scale, mask, sv, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=1e-5, atol=1e-5)
    # and the unfused pallas pipeline (dequant kernel -> screen kernel) too
    staged = ops.trimmed_mean(ops.dequant(msg.payload, msg.scale, block_d=128, interpret=True),
                              mask, sv, b, block_d=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(staged), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape", [(9, 130), (5, 257), (3, 9, 130)])
def test_fused_dequant_median_matches_reference(shape):
    from repro.kernels import ops, ref

    rng = np.random.default_rng(shape[-1])
    x = jnp.asarray(rng.normal(size=shape), jnp.float32)
    msg = get_codec("int8").encode(jax.random.PRNGKey(1), x)
    lead, d = shape[:-1], shape[-1]
    mask = jnp.asarray(rng.random(lead) < 0.7)
    mask = mask.at[..., 0].set(True)
    sv = jnp.asarray(rng.normal(size=shape[:-2] + (d,)), jnp.float32)
    out = ops.dequant_median(msg.payload, msg.scale, mask, sv, block_d=128, interpret=True)
    exp = ref.dequant_median_ref(msg.payload, msg.scale, mask, sv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# benchmarks.check_regression tooling
# ---------------------------------------------------------------------------


def _write(path, record):
    with open(path, "w") as f:
        json.dump(record, f)


def test_check_regression_missing_baseline_warns_not_fails(tmp_path, capsys):
    from benchmarks import check_regression as cr

    fresh = tmp_path / "fresh"
    base = tmp_path / "base"
    fresh.mkdir(), base.mkdir()
    _write(fresh / "BENCH_comm.json", {"grid": {"wall_s": 1.0}})
    rc = cr.main(["--fresh-dir", str(fresh), "--baseline-dir", str(base),
                  "--names", "BENCH_comm.json"])
    assert rc == 0  # new benchmark without a committed baseline never fails
    assert "no committed baseline" in capsys.readouterr().out


def test_check_regression_per_file_update_and_gate(tmp_path):
    from benchmarks import check_regression as cr

    fresh = tmp_path / "fresh"
    base = tmp_path / "base"
    fresh.mkdir(), base.mkdir()
    _write(fresh / "BENCH_comm.json", {"grid": {"wall_s": 1.0}})
    _write(fresh / "BENCH_grid.json", {"grid": {"wall_s": 1.0}})
    _write(base / "BENCH_grid.json", {"grid": {"wall_s": 2.0}})
    args = ["--fresh-dir", str(fresh), "--baseline-dir", str(base),
            "--names", "BENCH_comm.json,BENCH_grid.json"]
    # `--update BENCH_comm.json` re-baselines ONLY the named file
    assert cr.main(args + ["--update", "BENCH_comm.json"]) == 0
    assert (base / "BENCH_comm.json").exists()
    # a typo'd / out-of-scope update name is an error, not a silent no-op
    assert cr.main(args + ["--update", "BENCH_typo.json"]) == 1
    assert json.load(open(base / "BENCH_grid.json"))["grid"]["wall_s"] == 2.0
    # gate passes (fresh faster than baseline), then fails on regression
    assert cr.main(args) == 0
    _write(fresh / "BENCH_grid.json", {"grid": {"wall_s": 4.0}})
    assert cr.main(args + ["--tol", "1.5"]) == 1
    # higher-is-better speedup metrics regress downward
    _write(fresh / "BENCH_comm.json", {"kernel": {"fused_speedup_vs_staged": 2.0}})
    _write(base / "BENCH_comm.json", {"kernel": {"fused_speedup_vs_staged": 8.0}})
    _write(fresh / "BENCH_grid.json", {"grid": {"wall_s": 1.0}})
    assert cr.main(args + ["--tol", "1.5"]) == 1
