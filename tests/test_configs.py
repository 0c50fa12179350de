"""The assigned architecture table, asserted literally."""
import pytest

from repro.configs import ARCHS, SHAPES, cell_skip_reason

# (layers, d_model, heads, kv, d_ff, vocab) per the assignment
EXPECTED = {
    "mamba2-370m": (48, 1024, 0, 0, 0, 50280),
    "chameleon-34b": (48, 8192, 64, 8, 22016, 65536),
    "hymba-1.5b": (32, 1600, 25, 5, 5504, 32001),
    "starcoder2-15b": (40, 6144, 48, 4, 24576, 49152),
    "phi3-mini-3.8b": (32, 3072, 32, 32, 8192, 32064),
    "minicpm3-4b": (62, 2560, 40, 40, 6400, 73448),
    "internlm2-20b": (48, 6144, 48, 8, 16384, 92544),
    "hubert-xlarge": (48, 1280, 16, 16, 5120, 504),
    "dbrx-132b": (40, 6144, 48, 8, 10752, 100352),
    "granite-moe-3b-a800m": (32, 1536, 24, 8, 512, 49155),
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_exact_assigned_dims(name):
    c = ARCHS[name]
    l, d, h, kv, ff, v = EXPECTED[name]
    assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads,
            c.d_ff, c.vocab_size) == (l, d, h, kv, ff, v)


def test_special_fields():
    assert ARCHS["mamba2-370m"].ssm_state == 128
    assert ARCHS["hymba-1.5b"].ssm_state == 16
    assert ARCHS["dbrx-132b"].n_experts == 16
    assert ARCHS["dbrx-132b"].moe_top_k == 4
    assert ARCHS["granite-moe-3b-a800m"].n_experts == 40
    assert ARCHS["granite-moe-3b-a800m"].moe_top_k == 8
    assert ARCHS["hubert-xlarge"].is_encoder
    assert ARCHS["minicpm3-4b"].attn_type == "mla"
    assert ARCHS["hymba-1.5b"].sliding_window == 1024


def test_padding_for_tp16():
    h = ARCHS["hymba-1.5b"].padded_for_mesh(16)
    assert h.n_heads == 32 and h.n_heads % h.n_kv_heads == 0
    assert h.real_n_heads == 25
    assert h.vocab_size % 16 == 0 and h.real_vocab_size == 32001
    g = ARCHS["granite-moe-3b-a800m"].padded_for_mesh(16)
    assert g.n_experts == 48 and g.real_n_experts == 40
    m = ARCHS["minicpm3-4b"].padded_for_mesh(16)
    assert m.n_heads == 48 and m.real_n_heads == 40
    p = ARCHS["phi3-mini-3.8b"].padded_for_mesh(16)
    assert p.n_heads == 32 and p.real_n_heads == 0  # no padding needed


def test_skip_rules():
    # long_500k: only SSM/hybrid run it
    runs_long = [n for n, c in ARCHS.items()
                 if cell_skip_reason(c, SHAPES["long_500k"]) is None]
    assert sorted(runs_long) == ["hymba-1.5b", "mamba2-370m"]
    # encoder has no decode
    assert cell_skip_reason(ARCHS["hubert-xlarge"], SHAPES["decode_32k"])
    assert cell_skip_reason(ARCHS["hubert-xlarge"], SHAPES["long_500k"])
    # everyone trains
    for c in ARCHS.values():
        assert cell_skip_reason(c, SHAPES["train_4k"]) is None


def test_param_counts_match_nameplates():
    # within 15% of the nameplate (naming conventions vary)
    plates = {"mamba2-370m": 0.37e9, "chameleon-34b": 34e9,
              "hymba-1.5b": 1.52e9, "starcoder2-15b": 15e9,
              "phi3-mini-3.8b": 3.8e9, "minicpm3-4b": 4e9,
              "internlm2-20b": 20e9, "hubert-xlarge": 0.96e9,
              "dbrx-132b": 132e9, "granite-moe-3b-a800m": 3.3e9}
    for name, plate in plates.items():
        got = ARCHS[name].n_params()
        assert abs(got - plate) / plate < 0.15, (name, got, plate)


@pytest.mark.parametrize("case,name", [("case1_4k", "case1"),
                                       ("case2_7k", "case2"),
                                       ("case3_16k", "case3")])
def test_lgrass_cases_match_generator(case, name):
    """The config's sizes are what official_case() generates."""
    from repro.configs.lgrass import CASES
    from repro.core.graph import official_case

    g = official_case(name)
    assert (CASES[case].n_nodes, CASES[case].n_edges) == (g.n, g.m)


@pytest.mark.parametrize("side,frac", [(5, 0.3), (17, 0.25), (40, 0.2)])
def test_powergrid_shape_matches_generator(side, frac):
    from repro.core.graph import powergrid_like_graph, powergrid_shape

    g = powergrid_like_graph(side, frac, seed=side)
    assert powergrid_shape(side, frac) == (g.n, g.m)
