"""The program's own spans and scopes, on the CPU.

* ``run_chunks`` writes ``jax.profiler`` host spans: ``bridge.run_chunks``
  around the call, and inside it per chunk ``bridge.put`` (``bytes``,
  ``ticks``), ``bridge.stack`` (``ticks``), ``bridge.dispatch`` (``lo``,
  ``hi``, ``traced``), ``bridge.flush`` (only with a writer or an event
  log), then one ``bridge.collect``;
* splitting the put from the stack leaves the loop's outputs bitwise those
  of the single ``stack_batches`` expression it replaced;
* the mesh step's compiled HLO carries ``mesh.grad`` / ``mesh.gather`` /
  ``mesh.screen`` in its ``op_name``s, and the streaming tick's reshapes
  of the parameter leaves lower under ``stream.layout``.
"""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BridgeConfig, BridgeTrainer, erdos_renyi, replicate
from repro.obs import EventLog
from repro.stream import StreamBridgeTrainer

M, T, CHUNK = 8, 10, 4
W = (2, 3)  # a node's leaf is 2-D, so the stream tick reshapes it to [M, 6]


def grad_fn(params, batch):
    diff = params["w"] - batch["c"]
    loss = 0.5 * batch["k"] * jnp.sum(diff ** 2)
    return loss, {"w": batch["k"] * diff}


def host_batches():
    rng = np.random.default_rng(3)
    return [{"c": rng.normal(size=(M, *W)).astype(np.float32),
             "k": rng.uniform(0.5, 1.5, size=(M,)).astype(np.float32)} for _ in range(T)]


def make(cls=BridgeTrainer):
    cfg = BridgeConfig(topology=erdos_renyi(M, 0.8, 1, seed=1), rule="trimmed_mean",
                       num_byzantine=1, attack="alie", lam=1.0, t0=10.0)
    tr = cls(cfg, grad_fn)
    st = tr.init(replicate({"w": jnp.zeros(W)}, M, perturb=0.1, key=jax.random.PRNGKey(0)),
                 seed=0)
    return tr, st


def host_spans(log_dir):
    """[(name, start_ns, end_ns, {stat: value})] of the ``bridge.*`` events
    on the host plane of the one trace under ``log_dir``."""
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    return sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                   for plane in pd.planes if plane.name == "/host:CPU"
                   for line in plane.lines for e in line.events
                   if e.name.startswith("bridge.")), key=lambda s: s[1])


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """One ``run_chunks`` call of T ticks in chunks of CHUNK under the
    profiler, with an event log: (its spans, the batches it was given)."""
    tr, st = make()
    batches = host_batches()
    log_dir = str(tmp_path_factory.mktemp("profile"))
    with EventLog(os.path.join(log_dir, "events.jsonl")) as ev:
        jax.profiler.start_trace(log_dir)
        try:
            st, ms = tr.run_chunks(st, lambda i: batches[i], T, chunk=CHUNK, events=ev)
            jax.block_until_ready((st, ms))
        finally:
            jax.profiler.stop_trace()
    return host_spans(log_dir), batches


def test_run_chunks_spans_nest_inside_the_call(profiled):
    spans, _ = profiled
    (outer,) = [s for s in spans if s[0] == "bridge.run_chunks"]
    assert outer[3] == {"lo": 0, "hi": T}
    inner = [s for s in spans if s is not outer]
    assert all(outer[1] <= s[1] and s[2] <= outer[2] for s in inner)
    chunks = -(-T // CHUNK)
    names = [s[0] for s in inner]
    for name in ("bridge.put", "bridge.stack", "bridge.dispatch", "bridge.flush"):
        assert names.count(name) == chunks, name
    assert names.count("bridge.collect") == 1 and names[-1] == "bridge.collect"
    # per chunk: put, then stack, then dispatch, then the flush
    assert names[:4] == ["bridge.put", "bridge.stack", "bridge.dispatch", "bridge.flush"]


def test_put_counts_the_host_bytes_and_dispatch_covers_the_ticks(profiled):
    spans, batches = profiled
    puts = [s[3] for s in spans if s[0] == "bridge.put"]
    assert sum(p["bytes"] for p in puts) == sum(
        b["c"].nbytes + b["k"].nbytes for b in batches)
    assert [p["ticks"] for p in puts] == [4, 4, 2]
    assert [s[3]["ticks"] for s in spans if s[0] == "bridge.stack"] == [4, 4, 2]
    ranges = [(s[3]["lo"], s[3]["hi"]) for s in spans if s[0] == "bridge.dispatch"]
    assert ranges == [(0, 4), (4, 8), (8, 10)]


def test_dispatch_marks_the_calls_that_traced(profiled):
    spans, _ = profiled
    # the first chunk of 4 traces the scan, the second reuses it, the ragged
    # tail of 2 traces once more
    assert [s[3]["traced"] for s in spans if s[0] == "bridge.dispatch"] == [1, 0, 1]


def test_no_flush_span_without_writer_or_events(tmp_path):
    tr, st = make()
    batches = host_batches()
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(tr.run_chunks(st, lambda i: batches[i], T, chunk=CHUNK))
    finally:
        jax.profiler.stop_trace()
    names = {s[0] for s in host_spans(str(tmp_path))}
    assert "bridge.flush" not in names and "bridge.dispatch" in names


def _loop_before_split(tr, state, batch_fn, num_steps, chunk):
    """The chunk loop as it was before the put and the stack were split:
    one ``jnp.stack([jnp.asarray(x) ...])`` per leaf."""
    scan_chunk = tr._chunk_scan()
    chunks_ms, done = [], 0
    while done < num_steps:
        hi = min(done + chunk, num_steps)
        batches = [batch_fn(done + i) for i in range(hi - done)]
        xs = jax.tree_util.tree_map(
            lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *batches)
        state, ms = scan_chunk(tr._cell, state, xs)
        chunks_ms.append(ms)
        done = hi
    return state, jax.tree_util.tree_map(lambda *xs: jnp.concatenate(xs, axis=0), *chunks_ms)


@pytest.mark.parametrize("cls", [BridgeTrainer, StreamBridgeTrainer])
def test_run_chunks_bitwise_the_loop_before_the_split(cls):
    batches = host_batches()
    tr, st = make(cls)
    got_st, got_ms = tr.run_chunks(st, lambda i: batches[i], T, chunk=CHUNK)
    tr, st = make(cls)
    if cls is StreamBridgeTrainer:
        tr._build(st.params)
    ref_st, ref_ms = _loop_before_split(tr, st, lambda i: batches[i], T, CHUNK)
    for got, ref in ((got_st.params, ref_st.params), (got_ms, ref_ms)):
        for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(ref),
                        strict=True):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_mesh_step_hlo_carries_the_mesh_scopes():
    from jax.sharding import AxisType

    from repro.configs import get_config
    from repro.configs.shapes import InputShape, train_specs
    from repro.core.graph import complete_graph
    from repro.launch import sharding
    from repro.launch.steps import make_train_step
    from repro.models import api as model_api

    mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    nax = ("data",)
    cfg = get_config("qwen3-4b").reduced(num_layers=1)
    m = 3
    pshapes = jax.eval_shape(
        lambda k: replicate(model_api.build(cfg).init_params(k, cfg), m), jax.random.PRNGKey(0))
    pspecs = sharding.param_specs(cfg, pshapes, node_axes=nax)
    batch = train_specs(cfg, InputShape("tiny", 16, m, "train"), m)
    step = make_train_step(cfg, mesh, nax, pspecs, jnp.asarray(complete_graph(m, 1).adjacency),
                           rule="trimmed_mean", num_byzantine=1)
    in_sh = (sharding.named(mesh, pspecs),
             sharding.named(mesh, sharding.train_batch_specs(batch, nax)), None)
    with mesh:
        txt = jax.jit(step, in_shardings=in_sh).lower(
            pshapes, batch, jax.ShapeDtypeStruct((), jnp.float32)).compile().as_text()
    for scope in ("mesh.grad", "mesh.gather", "mesh.screen"):
        assert f"/{scope}/" in txt, scope


def test_stream_step_hlo_carries_the_layout_scope():
    tr, st = make(StreamBridgeTrainer)
    tr._build(st.params)
    xs = {"c": jnp.zeros((1, M, *W)), "k": jnp.ones((1, M))}
    # the lowered program's locations: the CPU compiles these reshapes to
    # bitcasts, which carry no metadata (on the TPU's tiled layouts they are
    # copies, named by the same scope)
    txt = tr._chunk_scan().lower(tr._cell, st, xs).as_text(debug_info=True)
    assert 'loc("stream.layout/reshape"' in txt


_CACHE_PROBE = """
import sys
import jax, jax.numpy as jnp
from repro.launch.cache import use_compilation_cache
use_compilation_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
events = []
jax.monitoring.register_event_listener(lambda e, **kw: events.append(e))
def f(x):
    with jax.named_scope(sys.argv[1]):
        return jnp.sin(x) * 2
step = jax.jit(f)
def call(x):
    return step(x).block_until_ready()
def lower(x):
    return step.lower(x).compile().as_text()
text = (call if sys.argv[2] == "call" else lower)(jnp.ones(8))
print("hits", sum(e.endswith("cache_hits") for e in events),
      "misses", sum(e.endswith("cache_misses") for e in events))
print(text if sys.argv[2] == "lower" else "")
"""


def test_compile_cache_keys_on_the_scope_names(tmp_path):
    """A cached executable keeps the op names it was compiled with, so the
    persistent cache (`repro.launch.cache`) keys on them: a change of scope
    alone compiles anew, the same program from another caller finds its
    entry, and the scopes stay in the compiled ``op_name``s."""
    import subprocess
    import sys

    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path), JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([src, os.environ.get("PYTHONPATH", "")]))

    def run(scope, how):
        out = subprocess.run([sys.executable, "-c", _CACHE_PROBE, scope, how], env=env,
                             capture_output=True, text=True, timeout=300, check=True)
        words = out.stdout.split()
        return int(words[1]), int(words[3]), out.stdout

    first = run("mesh.screen", "call")
    assert first[1] >= 1  # an empty cache: the program compiles
    assert run("mesh.screen", "lower")[:2] == (sum(first[:2]), 0)  # another caller: all hit
    assert run("mesh.gather", "call")[1] == 1  # a new scope name: the program alone misses
    assert "/mesh.gather/" in run("mesh.gather", "lower")[2]
