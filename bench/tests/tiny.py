"""Each cell's path at a size the CPU runs in seconds: the same drivers,
references and comparison as the chip run, with small widths and nodes."""
import os
import time

import jax

from bench import common, run

TINY_LM = {
    "name": "tiny-lm", "model": "dense_lm", "model_type": "qwen3", "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 1, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256, "rms_norm_eps": 1e-06,
    "rope_theta": 1000000, "dtype": "float32",
}
# path (or a variant of its cell) -> (configuration, traffic, limits)
CELLS = {
    "paper": ("mnist-linear", "m128k16", "paper-linear.m128k16"),
    "stream": ("qwen3-4b-l1v8", "stream-t2048", "qwen3-4b-l1v8.stream-t2048"),
    "stream-b1": ("qwen3-4b-l1v8", "stream-t2048", "qwen3-4b-l1v8.stream-t2048"),
    "mesh": ("qwen3-4b-l1v8", "mesh4-t2048", "qwen3-4b-l1v8.mesh4-t2048"),
}


def files(path: str):
    """(work, cfg, traffic, limits) of the cell on ``path``, shrunk."""
    conf, traffic, limits = CELLS[path]
    b = os.path.join(common.BENCH, "{}", "{}.json").format
    tr = common.load_json(b("traffic", traffic))
    limits = common.load_json(b("limits", limits))
    work = {"name": f"{path}-tiny", "chips": 4 if path == "mesh" else 1}
    if path == "paper":
        cfg = common.load_json(b("configs", conf))
        tr.update(nodes=16, b=1, byzantine=1, batch=8, ticks_per_call=5, trace_calls=1,
                  graph={"kind": "small_world", "nearest": 3, "rewire": 0.2,
                         "max_degree": 10, "seed": 0})
    else:
        cfg = dict(TINY_LM)
        tr.update(seq=32, trace_calls=1)
        if tr["path"] == "stream":
            tr.update(ticks_per_call=2)
        if path == "stream-b1":  # one row a node: the faults halve its sequence
            tr.update(batch=1)
    return work, cfg, tr, limits


def run_tiny(path: str, *, seed: int = 2**33 + 7, cell_cls=None):
    """One run of the shrunk cell on the CPU; returns the result object."""
    import importlib

    work, cfg, tr, limits = files(path)
    cls = cell_cls or importlib.import_module(f"bench.paths.{tr['path']}").Cell
    return run.run_cell(work, cfg, tr, limits, seed=seed, seconds=0.5, trace=False,
                        devices=jax.devices(), peak=common.peaks("TPU v5 lite"),
                        t_start=time.perf_counter(), metric_entries=[], cell=cls(cfg, tr, seed))
