"""Cross-layer integration: block-selection -> Pallas kernel, specs table,
roofline formatter, engine determinism."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import ASSIGNED, get_config
from repro.core.fusion import GlassConfig, glass_scores, select_blocks
from repro.kernels.glass_ffn import glass_ffn_block_sparse
from repro.launch.specs import SHAPES, applicable_shapes, compact_config
from repro.models.ffn import ffn_forward, init_ffn
from repro.models.common import ModelConfig


def test_block_selection_feeds_kernel():
    """GLASS block selection -> Pallas block-sparse kernel == masked dense FFN."""
    cfg = ModelConfig(d_model=128, d_ff=512, dtype="float32")
    p = init_ffn(jax.random.key(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (4, 128))
    local = jnp.abs(jax.random.normal(jax.random.key(2), (512,)))
    glob = jnp.abs(jax.random.normal(jax.random.key(3), (512,)))
    scores = glass_scores(local, glob, lam=0.5)
    bidx, mask = select_blocks(scores, k=256, block_size=128)
    out_kernel = glass_ffn_block_sparse(
        x, p["w_up"], p["w_down"], bidx, p["w_gate"], act="silu", block_size=128, interpret=True
    )
    out_masked = ffn_forward(p, x, cfg, mask=mask)
    np.testing.assert_allclose(np.asarray(out_kernel), np.asarray(out_masked), atol=2e-5, rtol=2e-5)


def test_applicable_shapes_policy():
    cells = 0
    for arch in ASSIGNED:
        cfg = get_config(arch)
        shapes = applicable_shapes(cfg)
        cells += len(shapes)
        if cfg.family in ("ssm", "hybrid"):
            assert "long_500k" in shapes
        else:
            assert "long_500k" not in shapes
    assert cells == 32  # 10 archs x 3 + 2 sub-quadratic long-context cells


def test_compact_config_divisibility():
    """50% compact widths stay shardable over the 16-wide model axis."""
    for arch in ASSIGNED:
        cfg = get_config(arch)
        dcfg = compact_config(cfg, 0.5)
        assert dcfg.d_ff == cfg.d_ff // 2
        assert dcfg.d_ff % 16 == 0, arch


def test_roofline_formatter(tmp_path):
    import json
    from benchmarks.roofline import fmt_table, load_records
    rec = {
        "arch": "x", "shape": "train_4k", "mesh": {"data": 16, "model": 16},
        "roofline_terms_s": {"compute_s": 1.0, "memory_s": 0.1, "collective_s": 2.0},
        "bottleneck": "collective_s", "useful_flops_ratio": 0.5,
        "memory": {"peak_bytes": 2 * 1024**3}, "fits_hbm_16g": True,
    }
    (tmp_path / "a.json").write_text(json.dumps(rec))
    out = fmt_table(load_records(tmp_path))
    assert "collective" in out and "0.50" in out
    csv = fmt_table(load_records(tmp_path), csv=True)
    assert csv.splitlines()[0].startswith("arch,")


@given(st.text(max_size=64))
@settings(max_examples=50, deadline=None)
def test_tokenizer_roundtrip_property(s):
    from repro.data.tokenizer import decode, encode
    assert decode(encode(s)) == s
