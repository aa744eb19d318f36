"""The work counts (`bench.work`) against the program's own parameter count
and the cells' shapes."""
import os

import jax
import pytest

from bench import common, weights, work
from bench.models import dense_lm


@pytest.fixture(scope="module")
def qwen():
    return common.cell_files("qwen3-4b-l1v8.stream-t2048")


def test_weight_plan_matches_program_param_count(qwen):
    from repro.models import api

    _, cfg, _, _ = qwen
    assert weights.count(cfg) == api.param_count(dense_lm.program_config(cfg, "float32"))
    assert weights.count(cfg) == 198_172_416


def test_weight_plan_matches_program_layout(qwen):
    from repro.core import replicate
    from repro.models import api

    _, cfg, tr, _ = qwen
    mc = dense_lm.program_config(cfg, "float32")
    layout = jax.eval_shape(lambda k: replicate(api.build(mc).init_params(k, mc), tr["nodes"]),
                            jax.random.PRNGKey(0))
    ours = jax.eval_shape(weights.make_init(cfg, tr["nodes"], 0.0, "float32"), jax.random.PRNGKey(0))
    weights.check_layout(ours, layout)


def test_matmul_params_are_all_but_embedding_and_norms(qwen):
    _, cfg, _, _ = qwen
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    norms = cfg["num_hidden_layers"] * (2 * d + 2 * hd) + d
    assert dense_lm.matmul_params(cfg) == weights.count(cfg) - cfg["vocab_size"] * d - norms


def test_flops_per_token_by_hand(qwen):
    _, cfg, tr, _ = qwen
    # wq, wo 2560x4096; wk, wv 2560x1024; 3 MLP matrices 2560x9728; head 2560x18992
    mm = 2 * 2560 * 4096 + 2 * 2560 * 1024 + 3 * 2560 * 9728 + 2560 * 18992
    attn = 2 * 2 * 32 * 128 * (1024 + 1) / 2
    assert work.train_flops_per_token(cfg, tr["seq"]) == pytest.approx(3 * (2 * mm + attn))
    assert work.train_flops_per_token(cfg, tr["seq"]) == pytest.approx(0.922e9, rel=1e-3)


def test_tick_counts_of_each_traffic():
    for conf, traffic in (("mnist-linear", "m128k16"), ("qwen3-4b-l1v8", "stream-t2048"),
                          ("qwen3-4b-l1v8", "mesh4-t2048")):
        cfg = common.load_json(os.path.join(common.BENCH, "configs", f"{conf}.json"))
        tr = common.load_json(os.path.join(common.BENCH, "traffic", f"{traffic}.json"))
        c = work.tick_counts(cfg, tr)
        m, d = tr["nodes"], weights.count(cfg)
        assert c["screen_bytes"] == 2 * m * d * 4
        if cfg["model"] == "linear":
            assert d == 7850
            assert c["tokens"] == m * tr["batch"]
            assert c["flops"] == m * tr["batch"] * 2 * 2 * 7840
        else:
            assert c["tokens"] == m * tr["batch"] * tr["seq"] == 8192
            assert c["bytes"] == 4 * m * d * 4 + m * tr["batch"] * (tr["seq"] + 1) * 4
