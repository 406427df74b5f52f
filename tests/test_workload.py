"""Workload profiler (paper step 1) consistency tests."""
import numpy as np
import pytest

import jax

from repro.configs import ARCHS, SHAPES, get_shape
from repro.core.workload import (
    INPUT_SIZE_CASES,
    ctc_stats,
    lm_block_ops,
    model_flops,
    profile_arch,
    total_ops,
    vgg16_conv,
)
from repro.models import abstract_params

# published parameter counts (B) — the analytic counter must land close
PUBLISHED_B = {
    "mixtral-8x22b": 141.0, "qwen2-moe-a2.7b": 14.3, "chatglm3-6b": 6.2,
    "stablelm-12b": 12.1, "minicpm-2b": 2.7, "starcoder2-3b": 3.0,
    "qwen2-vl-7b": 7.6, "hubert-xlarge": 0.96, "zamba2-2.7b": 2.7,
    "mamba2-1.3b": 1.3, "mellum2-12b-a2.5b": 12.0,
}


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_param_count_matches_real_model(arch):
    """cfg.param_count() (drives 6ND rooflines + HBM footprints) must
    equal the actual parameter tree within 0.1%."""
    cfg = ARCHS[arch]
    tree = abstract_params(cfg)
    actual = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(tree))
    assert abs(cfg.param_count() - actual) / actual < 1e-3


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_param_count_near_published(arch):
    got = ARCHS[arch].param_count() / 1e9
    want = PUBLISHED_B[arch]
    assert 0.75 * want <= got <= 1.35 * want, (got, want)


@pytest.mark.parametrize("arch", sorted(ARCHS))
@pytest.mark.parametrize("shape", ["train_4k", "prefill_32k"])
def test_profile_flops_bracket_model_flops(arch, shape):
    """Profiled forward FLOPs must track 2*N*D within a sane band
    (attention adds, MoE inactive experts subtract)."""
    cfg = ARCHS[arch]
    sh = get_shape(shape)
    ops = profile_arch(cfg, sh)
    fwd = sum(o.flops for o in ops)
    mf = model_flops(cfg, sh)
    fwd_model = mf if shape != "train_4k" else mf / 3.0
    # upper band 8x: long-context attention legitimately dominates 2ND
    # for small-d encoders (hubert @32k: full bidirectional kv)
    assert 0.5 * fwd_model <= fwd <= 8.0 * fwd_model, (fwd, fwd_model)


def test_ctc_monotone_in_input_size():
    meds = [ctc_stats(vgg16_conv(s))["median"] for s in INPUT_SIZE_CASES]
    assert all(b >= a for a, b in zip(meds, meds[1:]))


def test_vgg16_total_ops_sane():
    # VGG16 conv trunk @224 is ~30.7 GOP (2x 15.3 GMAC)
    ops = total_ops(vgg16_conv(224)) / 1e9
    assert 28.0 <= ops <= 33.0


def test_decode_ops_use_one_token():
    cfg = ARCHS["chatglm3-6b"]
    sh = get_shape("decode_32k")
    ops = lm_block_ops(cfg, sh.seq_len, sh.global_batch, "decode")
    qkv = next(o for o in ops if o.name == "L0.qkv")
    # decode qkv flops scale with batch (one token each), not batch*seq
    assert qkv.flops < 2 * sh.global_batch * cfg.d_model * \
        (cfg.n_heads + 2 * cfg.n_kv_heads) * cfg.head_dim * 1.01
