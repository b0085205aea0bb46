"""The ``kanana2-w2-sync`` cell's family at a tiny size on the CPU: the
program against the reference through the harness (``run.make_inputs``,
``build_sim``, ``compare.numbers``) reads ``correct`` within the cell's own
limits, the bfloat16 control fails one of them, the configuration holds the
published widths, and the two sides count the same FLOPs."""
import json

import numpy as np
import pytest

from bench import flops, readings, run
from bench.models import family

CELL = "kanana2-w2-sync"
SEED = 2 ** 31 + 977


def _tiny(per_worker=64):
    """At 64 sequences a worker (16 steps a round) the bfloat16 control's
    lost updates add up past the round-4 limit; at 16 they do not."""
    wl, cfg = run.load_cell(CELL)
    cfg = dict(cfg, train_per_worker=per_worker, test_size=4)
    cfg["model"] = family(cfg["model"]["kind"]).tiny(cfg["model"])
    return wl, cfg


@pytest.fixture(scope="module")
def read():
    wl, cfg = _tiny()
    run.start_jax(1, require_chip=False)
    return wl, readings.read_seed(wl, cfg, SEED, controls=("bf16",))


def _failed(numbers, limits):
    return [k for k, lim in limits.items() if k != "repeat_gap" and numbers[k] > lim]


def test_program_is_correct_within_the_cells_limits(read):
    wl, out = read
    assert _failed(out["program"], wl["limits"]) == [], out["program"]


def test_bf16_control_fails_a_limit(read):
    wl, out = read
    assert _failed(out["bf16"], wl["limits"]), out["bf16"]


def test_the_configuration_holds_the_published_widths():
    _, cfg = run.load_cell(CELL)
    m = cfg["model"]
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size", "kv_lora_rank",
                "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "num_experts_per_tok",
                "n_shared_experts", "routed_scaling_factor", "first_k_dense_replace",
                "rms_norm_eps", "rope_theta", "norm_topk_prob", "num_hidden_layers",
                "num_attention_heads", "vocab_size"):
        assert m[key] == cfg[key], key
    # the file's expert count is the experts held here; the router keeps the
    # published count
    assert cfg["n_routed_experts"] == m["experts_held"]
    assert m["n_routed_experts"] == cfg["published"]["n_routed_experts"] == 128
    assert cfg["qk_head_dim"] == m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    assert cfg["q_lora_rank"] is None and cfg["scoring_func"] == "sigmoid"
    assert cfg["num_key_value_heads"] == cfg["num_attention_heads"]
    assert set(cfg["reduced"]) == {"num_hidden_layers", "n_routed_experts",
                                   "num_attention_heads", "num_key_value_heads", "vocab_size",
                                   "rounds", "prune_interval"}
    manifest = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in manifest["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"]


def test_forward_flops_match_program_at_random_widths():
    import jax

    from repro.core.aggregation import subparam_shapes

    _, cfg = _tiny()
    model = cfg["model"]
    net = family("deepseek_v3").program_model("t", model)["model"]
    params = net.init(jax.random.PRNGKey(0))
    space, unit_map = net.unit_space(params)
    units = family("deepseek_v3").units(model)
    assert [(l.name, l.num_units) for l in space.layers] == [(n, c) for n, c, _ in units]
    base = {k: v.shape for k, v in params.items()}
    rng = np.random.default_rng(0)
    for _ in range(5):
        index = {l.name: np.sort(rng.choice(l.num_units, rng.integers(2, l.num_units + 1),
                                            replace=False)) for l in space.layers}
        kept = {k: len(v) for k, v in index.items()}
        want = net.flops_from_shapes(subparam_shapes(index, unit_map, base))
        assert flops.forward_flops(model, kept) == want
    assert flops.forward_flops(model) == net.flops_from_shapes(base)


def test_expert_readers_read_counters_and_grouped_matmuls():
    from types import SimpleNamespace as NS

    _, cfg = run.load_cell(CELL)
    sims = [NS(counters={"held_tokens": 800.0, "held_load_max": 150.0}),
            NS(counters={"held_tokens": 800.0, "held_load_max": 250.0})]
    ops = {"ragged-dot-none.3 [pruned_matmul]": 0.25, "ragged-dot-metadata.1 [pruned_matmul]": 0.05,
           "fusion.7": 9.0}
    r = NS(cfg=cfg, sims=sims, trace=NS(op_s=ops), traced_rounds=6)
    # 8 experts held: the mean load is 1600 / 8 = 200 tokens against the summed maxima 400
    assert run.reader("expert_load_max_over_mean")(r) == 400 / 200
    assert run.reader("expert_ms_per_round")(r) == pytest.approx(1e3 * 0.3 / 6)
    # a program without the counters or the grouped matmul reads nothing
    parent = NS(cfg=cfg, sims=[NS(), NS()], trace=NS(op_s={"fusion.7": 9.0}), traced_rounds=6)
    assert run.reader("expert_load_max_over_mean")(parent) is None
    assert run.reader("expert_ms_per_round")(parent) is None
