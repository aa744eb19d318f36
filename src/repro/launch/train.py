"""End-to-end decentralized training driver (runs for real on local devices).

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --reduce \
        --nodes 6 --byzantine 1 --attack random --rule trimmed_mean \
        --steps 100 --batch 4 --seq 128

``--reduce`` swaps in the reduced config (CPU-runnable); without it the
published config is used, and every node holds a full replica of it, so it
needs accelerator memory for ``--nodes`` copies of the parameters and their
gradients (``chip_smoke.py`` runs qwen3-4b at published widths, with depth
and vocabulary cut, on one chip).  Supports checkpoint save/resume.  The
persistent compilation cache goes where ``JAX_COMPILATION_CACHE_DIR`` says,
else to ``<repo>/.jax_cache`` (`repro.launch.cache`).

Network scenarios (repro.net): ``--net`` routes training through the
unreliable-network runtime; combine with ``--net-drop 0.2 --net-latency 3
--net-schedule churn`` etc.  Message-granularity attacks (selective_victim)
imply ``--net``.

Observability (repro.obs): ``--trace DIR`` compiles screening forensics into
the step (bit-inert), streams a JSONL event log to ``DIR/events.jsonl``, and
dumps ``DIR/obs_summary.json`` for ``python -m repro.obs.report DIR``.
``--profile DIR`` captures a ``jax.profiler`` trace of the training loop
(named scopes mark the gather/screen/apply/codec phases).
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint
from repro.configs import get_config
from repro.core import BridgeConfig, BridgeTrainer, erdos_renyi, replicate
from repro.core.byzantine import ATTACKS
from repro.data.tokens import TokenPipeline
from repro.launch.cache import use_compilation_cache
from repro.models import api as model_api


def build_trainer(args, topo, grad_fn):
    """BridgeTrainer (synchronous) or AsyncBridgeTrainer (--net scenarios)."""
    from repro.core.byzantine import WIRE_ATTACKS

    trace = None
    if args.trace is not None:
        from repro.obs import TraceSpec

        trace = TraceSpec(reservoir=args.trace_reservoir)
    trust = None
    if args.trust:
        from repro.trust import TrustSpec

        trust = TrustSpec(evict_threshold=args.trust_evict,
                          warmup=args.trust_warmup,
                          echo=not args.trust_no_echo)
    mspec = None
    if args.metrics is not None:
        from repro.obs import MetricSpec

        mspec = MetricSpec(capacity=args.metrics_capacity)
    use_net = args.net or (args.attack not in ATTACKS and args.attack not in WIRE_ATTACKS)
    if not use_net:
        bcfg = BridgeConfig(
            topology=topo, rule=args.rule, num_byzantine=args.byzantine,
            attack=args.attack, adversary=args.adversary, codec=args.codec,
            lam=args.lam, t0=args.t0, lr=args.lr, sparse=args.sparse,
            trace=trace, trust=trust, metrics=mspec,
        )
        return BridgeTrainer(bcfg, grad_fn)
    from repro.net import AsyncBridgeConfig, AsyncBridgeTrainer, ChannelConfig
    from repro.net.dynamic import scenario_schedule

    channel = ChannelConfig(
        drop_prob=args.net_drop,
        latency_min=args.net_latency_min,
        latency_max=args.net_latency,
        bandwidth_cap=args.net_cap,
    )
    acfg = AsyncBridgeConfig(
        topology=topo, rule=args.rule, num_byzantine=args.byzantine,
        attack=args.attack, adversary=args.adversary, codec=args.codec,
        lam=args.lam, t0=args.t0, lr=args.lr, sparse=args.sparse,
        channel=channel, staleness_bound=args.net_staleness,
        schedule=scenario_schedule(args.net_schedule, topo, args.steps,
                                   seed=args.seed, churn_prob=args.net_churn_prob),
        trace=trace, trust=trust, metrics=mspec,
    )
    return AsyncBridgeTrainer(acfg, grad_fn)


def dump_obs(args, trainer, state, topo, events_path) -> str:
    """Render the final `TraceState` into ``obs_summary.json`` (the input of
    ``python -m repro.obs.report``)."""
    import json

    from repro.obs import trace as obs_trace

    m = args.nodes
    nbr = (trainer.neighbors if trainer.runtime is None
           else getattr(trainer.runtime, "neighbors", None))
    if nbr is not None:
        senders = obs_trace.sender_grid(m, neighbors=nbr)
    else:
        # net schedules vary per tick, so the mailbox width is the full grid
        senders = obs_trace.sender_grid(
            m, adjacency=None if trainer.runtime is not None else topo.adjacency)
    rec = obs_trace.summarize(trainer.config.trace, state.obs,
                              byz_mask=np.asarray(trainer.byz_mask), senders=senders)
    tag = f"{args.rule}_{args.attack}_b{args.byzantine}_s{args.seed}"
    summary = {"meta": {"nodes": m, "steps": args.steps, "rule": args.rule,
                        "attack": args.attack, "adversary": args.adversary,
                        "codec": args.codec, "events": events_path},
               "cells": [{"tag": tag, "rule": args.rule, **rec}]}
    path = os.path.join(args.trace, "obs_summary.json")
    with open(path, "w") as f:
        json.dump(summary, f, indent=2, sort_keys=True)
    return path


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduce", action="store_true")
    ap.add_argument("--nodes", type=int, default=6)
    ap.add_argument("--byzantine", type=int, default=1)
    ap.add_argument("--attack", default="none")
    ap.add_argument("--adversary", default="none",
                    help="adaptive adversary (repro.adversary): ipm, "
                         "alie_online, dissensus, inner_max, or any static "
                         "attack name (stateless tier)")
    ap.add_argument("--rule", default="trimmed_mean")
    ap.add_argument("--codec", default="identity",
                    help="wire codec (repro.comm): identity, int8, int4, "
                         "topk<P>[_int8|_int4], randk<P>[_int8|_int4]")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4, help="per-node batch")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lam", type=float, default=1.0)
    ap.add_argument("--t0", type=float, default=100.0)
    ap.add_argument("--lr", type=float, default=0.0, help="constant lr override")
    ap.add_argument("--graph-p", type=float, default=0.8)
    ap.add_argument("--topology", default=None,
                    help="named topology spec (repro.core.graph.TOPOLOGIES): "
                         "erdos_renyi[:p], small_world[:nearest], "
                         "geometric[:radius], torus[:rows], complete; "
                         "default builds ER from --graph-p")
    ap.add_argument("--sparse", action="store_true",
                    help="neighbor-indexed [M, K] state layout "
                         "(repro.core.neighbors) — bit-identical to dense, "
                         "required past a few hundred nodes")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    # network-scenario flags (repro.net)
    ap.add_argument("--net", action="store_true",
                    help="route training through the unreliable-network runtime")
    ap.add_argument("--net-drop", type=float, default=0.0, help="per-link drop probability")
    ap.add_argument("--net-latency", type=int, default=0, help="max link latency (ticks)")
    ap.add_argument("--net-latency-min", type=int, default=0)
    ap.add_argument("--net-cap", type=int, default=None, help="bandwidth cap (coordinates)")
    ap.add_argument("--net-staleness", type=int, default=5,
                    help="max usable message age (ticks)")
    ap.add_argument("--net-schedule", default="static",
                    choices=["static", "churn", "partition", "join_leave"])
    ap.add_argument("--net-churn-prob", type=float, default=0.2)
    # observability flags (repro.obs)
    ap.add_argument("--trace", default=None, metavar="DIR",
                    help="compile screening forensics into the step (bit-inert) "
                         "and write DIR/events.jsonl + DIR/obs_summary.json "
                         "(render with `python -m repro.obs.report DIR`)")
    ap.add_argument("--trace-reservoir", type=int, default=0,
                    help="raw-trace reservoir slots kept on device (0: "
                         "bounded aggregates only)")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of the training loop "
                         "into DIR (phases are jax.named_scope-annotated)")
    ap.add_argument("--metrics", default=None, metavar="DIR",
                    help="compile the live metric ring into the step "
                         "(bit-inert) and stream per-tick scalar rows to "
                         "DIR/metrics.jsonl via the chunked runner; watch "
                         "with `python -m repro.obs.monitor DIR`, export "
                         "with `python -m repro.obs.perfetto DIR`; pass the "
                         "same DIR as --trace to keep all artifacts together")
    ap.add_argument("--metrics-capacity", type=int, default=64,
                    help="on-device metric ring slots (= the chunked "
                         "runner's scan chunk length)")
    ap.add_argument("--wire-budget-bytes", type=float, default=None,
                    help="alert (obs.alert event) when cumulative wire bytes "
                         "cross this budget")
    # trust flags (repro.trust)
    ap.add_argument("--trust", action="store_true",
                    help="reputation-weighted screening + eviction "
                         "(repro.trust); pair with --rule rep_trimmed_mean / "
                         "rep_median for soft down-weighting, any rule gets "
                         "hard eviction")
    ap.add_argument("--trust-evict", type=float, default=0.5,
                    help="suspicion threshold that latches an edge out")
    ap.add_argument("--trust-warmup", type=int, default=8,
                    help="ticks before evictions can latch")
    ap.add_argument("--trust-no-echo", action="store_true",
                    help="disable the equivocation echo protocol (net path)")
    args = ap.parse_args(argv)
    use_compilation_cache()

    cfg = get_config(args.arch)
    if args.reduce:
        cfg = cfg.reduced()
    api = model_api.build(cfg)
    print(f"arch={cfg.name} family={cfg.family} params(single)="
          f"{model_api.param_count(cfg):,}")

    if args.topology:
        from repro.core.graph import make_topology

        topo = make_topology(args.topology, args.nodes, args.byzantine, seed=args.seed)
    else:
        topo = erdos_renyi(args.nodes, args.graph_p, args.byzantine, seed=args.seed)
    trainer = build_trainer(args, topo, api.grad_fn())
    key = jax.random.PRNGKey(args.seed)
    params = replicate(api.init_params(key, cfg), args.nodes, perturb=0.01, key=key)
    state = trainer.init(params, seed=args.seed)
    start = 0
    if args.ckpt and checkpoint.latest_step(args.ckpt) is not None:
        # Checkpoint the *full* BridgeState — including the PRNG key and any
        # network-runtime state (in-flight mailboxes) — so a resumed lossy run
        # replays the exact channel/attack trace of an uninterrupted one.
        try:
            restored, start = checkpoint.restore(args.ckpt, tuple(state))
            state = type(state)(*jax.tree_util.tree_map(jnp.asarray, restored))
        except ValueError:
            # legacy (params, t) checkpoints: resume params but warn that the
            # PRNG/network state restarts (loss trace won't replay exactly)
            (p, t), start = checkpoint.restore(args.ckpt, (state.params, state.t))
            state = state._replace(params=jax.tree_util.tree_map(jnp.asarray, p),
                                   t=jnp.asarray(t))
            print("legacy checkpoint format: PRNG key / network state reinitialized")
        print(f"resumed from step {start}")

    pipe = TokenPipeline(cfg.vocab_size, args.seq, args.batch, args.nodes, seed=args.seed)

    # run-bracket artifacts (repro.obs): one directory holds the event log,
    # the live metric stream, and the manifest — pass the same DIR to both
    # --trace and --metrics to keep everything together
    run_dir = args.trace or args.metrics
    events = None
    if run_dir is not None:
        from repro.obs import EventLog, write_manifest

        os.makedirs(run_dir, exist_ok=True)
        extra = {}
        if trainer.runtime is not None:
            extra["network"] = trainer.runtime.describe()
        write_manifest(run_dir, kind="train", config=vars(args), extra=extra)
        events = EventLog(os.path.join(run_dir, "events.jsonl"))
        events.emit("run.start", kind="train", arch=cfg.name, nodes=args.nodes,
                    steps=args.steps, rule=args.rule, attack=args.attack,
                    net=bool(trainer.runtime is not None), resumed_at=start)
    mwriter = None
    if args.metrics is not None:
        from repro.obs import AlertRules, MetricWriter

        os.makedirs(args.metrics, exist_ok=True)
        mwriter = MetricWriter(
            os.path.join(args.metrics, "metrics.jsonl"),
            alerts=AlertRules(wire_budget_bytes=args.wire_budget_bytes),
            events=events)
    if args.profile is not None:
        os.makedirs(args.profile, exist_ok=True)
        jax.profiler.start_trace(args.profile)

    t_run = time.time()
    compile_s = 0.0
    t_last = time.time()
    if mwriter is not None:
        # chunked tick loop: jitted scan chunks with donated carries, the
        # metric ring flushed to the writer thread after each chunk (the
        # blocking device_get overlaps the next chunk's compute)
        def batch_at(i):
            return jax.tree_util.tree_map(jnp.asarray, pipe.batch(i))

        seg = args.ckpt_every if args.ckpt else max(args.steps - start, 1)
        done = start
        while done < args.steps:
            n = min(seg, args.steps - done)
            state, ms = trainer.run_chunks(state, batch_at, n, writer=mwriter,
                                           events=events, start=done)
            if done == start:
                # the first segment's wall is compile + n steps: close
                # enough that the steady-state remainder is honest
                jax.block_until_ready(state.params)
                compile_s = time.time() - t_run
            done += n
            if args.ckpt:
                checkpoint.save(args.ckpt, done, tuple(state))
            dt = time.time() - t_last
            t_last = time.time()
            print(f"step {done:5d}  loss {float(ms['loss'][-1]):.4f}  "
                  f"consensus {float(ms['consensus_dist'][-1]):.4f}  "
                  f"rho {float(ms['rho'][-1]):.5f}  {dt/n:.2f}s/step",
                  flush=True)
    else:
        for step in range(start, args.steps):
            batch = jax.tree_util.tree_map(jnp.asarray, pipe.batch(step))
            state, metrics = trainer.step(state, batch)
            if step == start:
                # the first step's wall is compile + one step: close enough to
                # the compile cost that the steady-state remainder is honest
                jax.block_until_ready(state.params)
                compile_s = time.time() - t_run
            if (step + 1) % args.log_every == 0:
                dt = time.time() - t_last
                t_last = time.time()
                net = ""
                if "delivered_frac" in metrics:
                    net = (f"  delivered {float(metrics['delivered_frac']):.2f}"
                           f"  stale {float(metrics['mean_staleness']):.1f}")
                if args.codec != "identity" and "wire_bits_per_edge" in metrics:
                    net += f"  wire {float(metrics['wire_bits_per_edge'])/8:.0f}B/edge"
                print(
                    f"step {step+1:5d}  loss {float(metrics['loss']):.4f}  "
                    f"consensus {float(metrics['consensus_dist']):.4f}  "
                    f"rho {float(metrics['rho']):.5f}{net}  {dt/args.log_every:.2f}s/step",
                    flush=True,
                )
            if args.ckpt and (step + 1) % args.ckpt_every == 0:
                checkpoint.save(args.ckpt, step + 1, tuple(state))
    state = jax.block_until_ready(state)
    wall = time.time() - t_run
    if args.profile is not None:
        jax.profiler.stop_trace()
        if events is not None:
            events.emit("profile.capture", dir=args.profile)
        print(f"profiler trace -> {args.profile}")
    if mwriter is not None:
        mwriter.close()
        print(f"metric stream -> {os.path.join(args.metrics, 'metrics.jsonl')}  "
              f"(watch: python -m repro.obs.monitor {args.metrics})")
    if events is not None:
        events.emit("run.end", steps=args.steps - start, wall_s=wall,
                    compile_s=compile_s, steady_state_s=max(wall - compile_s, 0.0))
        if state.obs is not None:
            first_bad = int(np.asarray(state.obs.first_bad))
            if first_bad >= 0:
                events.emit("obs.divergence", cell="train", first_bad_tick=first_bad)
        events.close()
    if args.trace is not None:
        path = dump_obs(args, trainer, state, topo,
                        os.path.join(run_dir, "events.jsonl"))
        print(f"obs summary -> {path}  "
              f"(render: python -m repro.obs.report {args.trace})")
    if run_dir is not None:
        from repro.obs import write_manifest

        write_manifest(run_dir, extra={"ended": True, "wall_s": wall,
                                       "steps": args.steps})
    if args.trust:
        from repro.obs import trace as obs_trace
        from repro.trust import summarize as trust_summarize

        nbr = (trainer.neighbors if trainer.runtime is None
               else getattr(trainer.runtime, "neighbors", None))
        if nbr is not None:
            senders = obs_trace.sender_grid(args.nodes, neighbors=nbr)
        else:
            senders = obs_trace.sender_grid(
                args.nodes,
                adjacency=None if trainer.runtime is not None else topo.adjacency)
        rec = trust_summarize(trainer.config.trust, state.trust,
                              byz_mask=np.asarray(trainer.byz_mask), senders=senders)
        print(f"trust: evicted {rec['edges_evicted']} edges "
              f"(byz {rec.get('byz_evicted', 0)}, honest {rec.get('honest_evicted', 0)}, "
              f"max suspicion {rec['max_suspicion']:.2f})")
    print("done.")


if __name__ == "__main__":
    main()


# ---------------------------------------------------------------------------
# static-analysis contracts (checked by `python -m repro.analysis`)
# ---------------------------------------------------------------------------

from repro.analysis.contracts import Contract  # noqa: E402  (dependency-light)

CONTRACTS: tuple[Contract, ...] = (
    Contract(
        "launch.prng.seed_plumbing", "lint",
        "no naked jax.random.PRNGKey in src/ outside seed plumbing: every "
        "key descends from a plumbed seed argument, or the site carries an "
        "explicit (file, function) waiver below",
        params=(
            ("check", "seed_plumbing"),
            ("waivers", (
                # documented default init key (the paper's common-ball init)
                ("repro/core/bridge.py", "replicate"),
                # keyless leaf screening falls back to a fixed public key
                ("repro/core/gossip.py", "coordwise_gossip_leaf"),
                # shape-only lowering: the key is never run
                ("repro/launch/dryrun.py", "build_lowerable"),
                # eval_shape parameter count: abstract, nothing drawn
                ("repro/models/api.py", "param_count"),
            )),
        ),
    ),
)
