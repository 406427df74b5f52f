"""FLOP and byte functions of the dense architecture and the peaks
table, against totals counted by hand at one small shape."""
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import peaks  # noqa: E402
import run  # noqa: E402

DENSE = run.architecture({"reference": "dense"})
TINY = json.loads((Path(__file__).parent / "data" / "tiny.json")
                  .read_text())["model"]
# L=2, d=64, 4 heads, 2 kv heads, head 16, ff 128, vocab 256, untied,
# LayerNorm, plain GELU MLP.
LAYER = 64 * 64 * 2 + 64 * 32 * 2 + 2 * 64 * 128       # 28,672


def test_params_and_cache_bytes():
    m = DENSE.ModelCosts(TINY)
    assert m.layer_matmul_params == LAYER
    # layers + embedding + head + (2L + 1) LayerNorms of scale and bias
    assert m.params == 2 * LAYER + 2 * 64 * 256 + 5 * 2 * 64 == 90_752
    assert m.kv_bytes_per_token == 2 * 2 * 2 * 16 * 2 == 256


def test_flops():
    m = DENSE.ModelCosts(TINY)
    assert m.token_flops(10) == 2 * (2 * LAYER + 4 * 64 * 10) == 119_808
    assert m.logits_flops == 2 * 64 * 256
    # three prompt tokens at contexts 1, 2, 3, and one row of logits
    assert m.prefill_flops(3) == 2 * (2 * LAYER * 3 + 4 * 64 * 6) \
        + 32_768 == 379_904
    assert m.decode_flops([5, 7]) == 301_056


def test_decode_bytes_and_bound():
    m = DENSE.ModelCosts(TINY)
    weights = 2 * LAYER + 5 * 2 * 64 + 64 * 256 + 2 * 64   # head + 2 rows
    kv = (4 + 6) * 256 + 2 * 256
    assert m.decode_bytes([5, 7]) == 2 * weights + kv == 152_064
    peak = {"bf16_flops": 1e6, "hbm_bytes_per_s": 1e6}
    # 301,056 operations at 1e6/s outlast 152,064 bytes at 1e6/s
    assert m.decode_seconds_bound([5, 7], peak) == pytest.approx(0.301056)


def test_tied_table_is_read_once():
    tied = dict(TINY, tie_word_embeddings=True)
    m = DENSE.ModelCosts(tied)
    assert m.params == 90_752 - 64 * 256
    assert m.decode_bytes([5, 7]) == 152_064 - 2 * 2 * 64


def test_peaks_are_keyed_by_device_kind():
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


@pytest.mark.parametrize("name", ["starcoder2-3b", "minicpm-2b"])
def test_configuration_files_match_the_registry(name):
    """The benchmark's files are what the program runs: the registry
    entry, with the options the file sets."""
    from repro.configs import get_arch
    cfg_file = json.loads((Path(__file__).resolve().parents[1] / "configs"
                           / f"{name}.json").read_text())
    m = cfg_file["model"]
    cfg = run.architecture(cfg_file).program_config(
        get_arch(cfg_file["arch"]), m)
    assert cfg.rope_theta == m["rope_theta"]
    assert cfg.tie_embeddings is m["tie_word_embeddings"]
