"""What is specific to the four-chip cell ``train4-qwen2-0.5b-m4h2-s512``:
its configuration is Qwen2-0.5B as published, every width of the
16-layer cell's file at all 24 layers with nothing reduced, and its mix
puts one client on each of the cell's chips. (``test_files.py`` loads
every cell; ``test_mesh.py`` runs the four-chip step against the
reference at the tiny size.)"""
import pytest

from benchmarks.chip import program, spec
from benchmarks.chip.tests.tiny import ROOT

CELL = "train4-qwen2-0.5b-m4h2-s512"
WIDTHS = ("hidden_size", "intermediate_size", "num_attention_heads",
          "num_key_value_heads", "vocab_size", "rms_norm_eps", "rope_theta",
          "tie_word_embeddings", "dtype", "matmul_precision")


@pytest.fixture(scope="module")
def cells():
    return spec.load_cell(ROOT, CELL), spec.load_cell(
        ROOT, "train-qwen2-0.5b-m2h2-s512")


@pytest.mark.parametrize("key", WIDTHS)
def test_every_width_is_the_16_layer_files(cells, key):
    full, cut = cells
    assert full.config[key] == cut.config[key]


def test_it_runs_all_24_layers_with_nothing_reduced(cells):
    full, _ = cells
    entry, = [c for c in spec.load_benchmark(ROOT)["configs"]
              if c["name"] == full.config_name]
    assert entry["reduced"] == [] and full.config["published"] == {}
    assert full.config["num_hidden_layers"] == 24
    assert full.config["rope_theta"] == 1e6
    assert full.config["tie_word_embeddings"] is True
    cfg = program.model_config(full.config)
    assert (cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.n_heads,
            cfg.n_kv_heads, cfg.vocab_size) == (24, 896, 4864, 14, 2, 151936)
    assert cfg.rope_theta == 1e6 and cfg.tie_embeddings and cfg.qkv_bias


def test_one_client_a_chip(cells):
    full, _ = cells
    assert full.chips == 4 and full.mix.clients == full.chips
    assert full.mix.tokens_per_round == 65536
