"""``harness/work_solar_open2.py`` against the sizes the issue reckoned, the
new cell's files, and the Solar Open 2 harness modules at the rehearsal's
sizes (by hand, on the CPU: see ``conftest.py``)."""
import json
import os

import numpy as np
import pytest

from harness import work_solar_open2 as W
from harness.builders import load_json, sizes_of

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "solar_open2.serve_doc_steady"


@pytest.fixture(scope="module")
def sizes():
    return sizes_of(load_json("configs", "solar-open2-250b"), False)


def test_parameters_and_bytes_of_the_share(sizes):
    per = W.params_per_sublayer(sizes)
    assert sum(per["K"]) == pytest.approx(137.7e6, rel=2e-3)   # KDA mixer
    assert sum(per["G"]) == pytest.approx(109.1e6, rel=2e-3)   # gated GQA
    assert sum(per["E"]) == pytest.approx(646.2e6, rel=2e-3)   # 40 held
    assert W.kinds(sizes) == {"G": 1, "K": 3}
    assert W.weight_bytes(sizes) == pytest.approx(6.62e9, rel=5e-3)
    assert W.kda_state_bytes(sizes) == 64 * 128 * 128 * 4 == 4_194_304
    assert W.expert_bytes(sizes) == 3 * 4096 * 1280 * 2 == 31_457_280
    assert W.kv_bytes_per_token(sizes) == 4096
    # a slot: three layers of state and of the convolution's window
    assert W.state_bytes_per_slot(sizes) == 3 * (4_194_304 + 3 * 24576 * 2)
    assert W.kda_step_bytes(sizes, 128) == 3 * 128 * 2 * 4_194_304
    # 128 slots of 5,120: state 1.61 GB + windows 57 MB + K/V 2.68 GB
    cache = 128 * (W.state_bytes_per_slot(sizes)
                   + 5120 * W.kv_bytes_per_token(sizes))
    assert cache == pytest.approx(4.35e9, rel=5e-3)


def test_flops(sizes):
    assert W.expert_pair_flops(sizes) == 6 * 4096 * 1280
    assert W.head_flops(sizes) == 2 * 24576 * 4096
    assert W.attn_flops(sizes, 10) == 4 * 64 * 128 * 10
    # a position: the mixers, the router and the shared expert of 4 layers,
    # 2 FLOPs a parameter that a token meets, and 7 a state element
    per = W.params_per_sublayer(sizes)
    met = 3 * per["K"][0] + per["G"][0] + 4 * (
        3 * 4096 * 1280 + 320 * 4096)
    want = 2 * met + 3 * 7 * 64 * 128 * 128
    assert W.position_flops(sizes) == pytest.approx(want, rel=2e-3)
    assert W.serve_flops(sizes, 1, 0, 0, 0) == W.position_flops(sizes)
    assert W.serve_flops(sizes, 0, 0, 1, 8) == W.head_flops(sizes) \
        + 8 * W.expert_pair_flops(sizes)


def test_decode_step_bytes(sizes):
    fixed = W.decode_step_fixed_bytes(sizes, 128)
    routed = 4 * 40 * W.expert_bytes(sizes)
    assert fixed == W.weight_bytes(sizes) - routed \
        + 2 * 128 * W.state_bytes_per_slot(sizes)
    # 3.2 GB of state read and written beside ~1.6 GB of weights
    assert fixed == pytest.approx(4.88e9, rel=2e-2)


def test_configuration_holds_the_published_numbers():
    cfg = load_json("configs", "solar-open2-250b")
    assert cfg["reduced"] == ["num_hidden_layers", "gqa_layers",
                              "n_routed_experts", "vocab_size"]
    assert cfg["published"] == {
        "num_hidden_layers": 48, "gqa_layers": list(range(0, 48, 4)),
        "n_routed_experts": 320, "vocab_size": 196608}
    assert (cfg["num_hidden_layers"], cfg["gqa_layers"],
            cfg["n_routed_experts"], cfg["vocab_size"]) == (4, [0], 40, 24576)
    for key, value in {
            "hidden_size": 4096, "moe_intermediate_size": 1280,
            "num_experts_per_tok": 8, "num_attention_heads": 64,
            "num_key_value_heads": 8, "head_dim": 128, "router_outputs": 320,
            "intermediate_size": 10240, "use_rope": False,
            "kda_allow_neg_eigval": True, "use_gqa_gate": True}.items():
        assert cfg[key] == value, key
    assert cfg["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None}
    assert all(isinstance(v, (str, int, dict))
               for v in cfg["assumed"].values())


def test_cell_names_its_metrics_and_the_manifest_lists_it():
    cell = load_json("workloads", CELL)
    assert cell["engine"] == {"max_batch": 128, "max_len": 5120}
    assert cell["traffic"]["set_seed"] == 34 and cell["drain"]
    assert cell["end_to_end"] == ["itl_p95_ms", "setup_s"]
    for name in cell["per_layer"]:
        spec = load_json("metrics", name)
        assert spec["moves"] == "itl_p95_ms", name
    with open(os.path.join(HERE, "..", "..", "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    assert manifest["workloads"][-1]["name"] == CELL
    listed = {m["name"] for m in manifest["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == set(cell["per_layer"])


def test_seeded_weights_fit_the_program_and_the_reference_at_tiny_sizes():
    import jax

    from harness import (builder_solar_open2 as B, reference_solar_open2 as R,
                         weights_solar_open2 as Wt)

    sizes = sizes_of(load_json("configs", "solar-open2-250b"), True)
    model = B.solar_open2_causal_lm(sizes, 7)
    ids = np.random.default_rng(0).integers(0, sizes["vocab_size"], 40)
    import paddle_tpu as paddle
    with paddle.no_grad():
        got = np.asarray(model(paddle.Tensor(ids[None]))._value[0])
    cfg, held = dict(R.flat(sizes)), tuple(range(sizes["n_routed_experts"]))
    top = Wt.top(7, sizes, sizes["dtype"])
    h = np.asarray(top["embed"])[ids]
    for i, kind in enumerate(Wt.kinds(sizes)):
        h = R._block(kind, h, R._layer_params(7, sizes, i, sizes["dtype"]),
                     R.flat(sizes), held, None)
    want = jax.jit(lambda h: R.head(h, top["norm_f"], top["head"], cfg))(h)
    np.testing.assert_allclose(got, want, atol=5e-4)
    # the token a greedy server would serve after 30 positions: no gap
    gaps = R.served_gaps(7, sizes, sizes["dtype"],
                         [(ids[:30].tolist(), [int(np.argmax(got[29]))])],
                         64, lowp="fp8")
    assert gaps[0][0] < 1e-3 and gaps[0][1] >= 0.0
