"""``harness/work_laguna.py`` against the sizes the issue reckoned, the new
cell's files, the two copies of the reference, and the Laguna harness
modules at the rehearsal's sizes (by hand, on the CPU: see ``conftest.py``)."""
import json
import os

import numpy as np
import pytest

from harness import work_laguna as W
from harness.builders import load_json, sizes_of

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "laguna_xs2.serve_mixed_steady"


@pytest.fixture(scope="module")
def sizes():
    return sizes_of(load_json("configs", "laguna-xs2"), False)


def test_parameters_and_bytes_of_the_share(sizes):
    # the issue's table
    assert W.attention_params(sizes, 48) == pytest.approx(29.46e6, rel=1e-3)
    assert W.attention_params(sizes, 64) == pytest.approx(37.88e6, rel=1e-3)
    assert W.dense_mlp_params(sizes) == 3 * 2048 * 8192
    assert W.expert_params(sizes) == 3 * 2048 * 512 == 3_145_728
    assert sum(W.expert_sublayer_params(sizes)) \
        == pytest.approx(104.3e6, rel=1e-3)               # 32 held
    assert sum(W.expert_sublayer_params(sizes, 256)) \
        == pytest.approx(809.0e6, rel=1e-3)               # whole
    assert W.kinds(sizes) == {"F": 4, "S": 9, "dense": 1, "sparse": 12}
    assert [k for k, _, _ in W.layers(sizes)] == list("FSSSFSSSFSSSF")
    assert {h for k, h, _ in W.layers(sizes) if k == "F"} == {48}
    assert {h for k, h, _ in W.layers(sizes) if k == "S"} == {64}
    assert W.weight_bytes(sizes) == pytest.approx(3.62e9, rel=5e-3)
    assert W.kv_row_bytes(sizes) == 4096
    assert W.expert_bytes(sizes) == 6_291_456
    # 48 slots of 9,216: 7.25 GB of full-length rows, 0.91 GB of rings
    assert W.cache_bytes(sizes, 48, 9216) == 48 * 4096 * (
        4 * 9216 + 9 * 512) == pytest.approx(8.15e9, rel=5e-3)
    # the whole model, as published: 33.4B, of which ~3.0 G meet a token
    whole = dict(sizes, num_hidden_layers=40, num_experts=256,
                 vocab_padded=100352)
    assert W.weight_bytes(whole) / 2 == pytest.approx(33.44e9, rel=2e-3)


def test_flops(sizes):
    assert W.expert_pair_flops(sizes) == 6 * 2048 * 512
    assert W.head_flops(sizes) == 2 * 12544 * 2048
    # full layers count the keys their queries saw, window layers the band
    assert W.attn_flops(sizes, 10, 0) == 4 * 4 * 48 * 128 * 10
    assert W.attn_flops(sizes, 0, 10) == 9 * 4 * 64 * 128 * 10
    assert W.band_flops(sizes, 7) == W.attn_flops(sizes, 0, 7)
    # a position of the share: 1.23 GFLOP with its routed experts (one pair
    # a sparse layer: 8 choices over 8 shares) and the head
    assert W.position_flops(sizes) == pytest.approx(1.106e9, rel=5e-3)
    assert W.serve_flops(sizes, 1, 0, 0, 1, 12) \
        == pytest.approx(1.23e9, rel=1e-2)
    assert W.serve_flops(sizes, 1, 0, 0, 0, 0) == W.position_flops(sizes)


def test_decode_step_bytes(sizes):
    fixed = W.decode_step_fixed_bytes(sizes)
    assert fixed == W.weight_bytes(sizes) - 12 * 32 * W.expert_bytes(sizes)
    assert fixed == pytest.approx(1.2e9, rel=3e-2)
    # 28 live slots of ~2,000 positions: 4 KB a live key position in each
    # full layer and a live ring row in each window layer
    assert W.live_kv_bytes(sizes, 56000, 28 * 512) \
        == 4096 * (4 * 56000 + 9 * 28 * 512)


def test_configuration_holds_the_published_numbers():
    cfg = load_json("configs", "laguna-xs2")
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (13, 32, 12544)
    assert cfg["published"]["num_hidden_layers"] == 40
    assert cfg["published"]["num_experts"] == cfg["router_outputs"] == 256
    assert cfg["published"]["vocab_size"] == 100352 == 8 * 12544
    for key, value in {
            "hidden_size": 2048, "intermediate_size": 8192,
            "moe_intermediate_size": 512, "num_experts_per_tok": 8,
            "shared_expert_intermediate_size": 512, "head_dim": 128,
            "num_attention_heads": 48, "num_key_value_heads": 8,
            "sliding_window": 512, "moe_routed_scaling_factor": 2.5,
            "gating": True, "rms_norm_eps": 1e-06,
            "max_position_embeddings": 262144}.items():
        assert cfg[key] == value, key
    # the published lists stand whole; the cut is their first 13 entries
    assert len(cfg["layer_types"]) == len(cfg["mlp_layer_types"]) \
        == len(cfg["num_attention_heads_per_layer"]) == 40
    assert cfg["layer_types"][:4] == ["full_attention"] \
        + ["sliding_attention"] * 3
    assert cfg["mlp_layer_types"][:2] == ["dense", "sparse"]
    assert cfg["num_attention_heads_per_layer"][:4] == [48, 64, 64, 64]
    full = cfg["rope_parameters"]["full_attention"]
    assert (full["rope_type"], full["factor"], full["beta_fast"],
            full["partial_rotary_factor"]) == ("yarn", 64, 64, 0.5)
    assert cfg["rope_parameters"]["sliding_attention"]["rope_theta"] == 10000
    assert all(isinstance(v, (str, int, dict))
               for v in cfg["assumed"].values())


def test_cell_names_its_metrics_and_the_manifest_lists_it():
    cell = load_json("workloads", CELL)
    assert cell["engine"] == {"max_batch": 48, "max_len": 9216}
    assert cell["traffic"]["set_seed"] == 36 and cell["drain"]
    assert cell["traffic"]["prompt_len"] == {
        "dist": "lognormal", "median": 1024, "sigma": 1.2, "min": 32,
        "max": 8192}
    assert cell["traffic"]["output_len"] == {
        "dist": "lognormal", "median": 320, "sigma": 0.6, "min": 64,
        "max": 1024}
    assert cell["end_to_end"] == ["itl_p95_ms", "setup_s"]
    for name in cell["per_layer"]:
        spec = load_json("metrics", name)
        assert spec["moves"] == "itl_p95_ms", name
    with open(os.path.join(HERE, "..", "..", "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    # (by name, not by place: a later cell is appended behind this one)
    assert CELL in [w["name"] for w in manifest["workloads"]]
    assert "laguna-xs2" in [c["name"] for c in manifest["configs"]]
    listed = {m["name"] for m in manifest["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == set(cell["per_layer"])


def test_knee_sweep_rows_are_the_cells_rates():
    rows = load_json("", "knee_sweep_mixed")
    rates = sorted({r["rate_per_s"] for r in rows if "backlog_at_open"
                    not in r})
    seeds = {r["seed"] for r in rows if "seed" in r}
    assert len(seeds) == 3 and len(rates) >= 3
    assert any(r.get("backlog_at_open") for r in rows)  # far above capacity
    rate = load_json("workloads", CELL)["traffic"]["rate_per_s"]
    assert any(abs(rate - share * knee) < 1e-6 for knee in rates
               for share in (0.8, 0.7))


def test_the_two_copies_of_the_reference_are_one_text():
    import inspect
    import sys

    from harness import reference_laguna as B

    sys.path.insert(0, os.path.join(HERE, "..", "..", "tests"))
    try:
        import reference_laguna as R
    finally:
        sys.path.pop(0)
    for name in ("rope_tables", "rotate", "attention", "route", "experts",
                 "swiglu_mlp", "plan", "block", "head", "unstack", "rms_norm",
                 "forward_held", "forward", "_fp8", "_mm", "silu"):
        assert inspect.getsource(getattr(R, name)) \
            == inspect.getsource(getattr(B, name)), name


def test_seeded_weights_fit_the_program_and_the_reference_at_tiny_sizes():
    import jax

    from harness import (builder_laguna as B, reference_laguna as R,
                         weights_laguna as Wt)

    sizes = sizes_of(load_json("configs", "laguna-xs2"), True)
    model = B.laguna_causal_lm(sizes, 7)
    ids = np.random.default_rng(0).integers(0, sizes["vocab_size"], 40)
    import paddle_tpu as paddle
    with paddle.no_grad():
        got = np.asarray(model(paddle.Tensor(ids[None]))._value[0])
    cfg, held = dict(R.flat(sizes)), tuple(range(sizes["num_experts"]))
    top = Wt.top(7, sizes, sizes["dtype"])
    h = np.asarray(top["embed"])[ids]
    for i, layer in enumerate(R.plan(sizes)):
        h = R._block(layer, h, R._layer_params(7, sizes, i, sizes["dtype"]),
                     R.flat(sizes), held, None)
    want = jax.jit(lambda h: R.head(h, top["norm_f"], top["head"], cfg))(h)
    np.testing.assert_allclose(got, want, atol=5e-4)
    # the token a greedy server would serve after 30 positions: no gap
    gaps = R.served_gaps(7, sizes, sizes["dtype"],
                         [(ids[:30].tolist(), [int(np.argmax(got[29]))])],
                         64, lowp="fp8")
    assert gaps[0][0] < 1e-3 and gaps[0][1] >= 0.0
