"""Fast pure-python coverage: input_specs for every (arch x shape),
applicability rules, MODEL_FLOPS/attention-skip math, timing model,
report generation on synthetic records."""
import json

import jax.numpy as jnp
import pytest

from repro.configs import (ARCH_IDS, SHAPES, get_config, get_smoke_config,
                           shape_applicable)
from repro.configs.base import ShapeConfig
from repro.models import input_specs
from repro.models.model import decode_cache_len
from repro.roofline.compose import (model_flops, attention_dense_flops,
                                    _attn_pair_fraction)
from repro.core.timing import Timeline, InterfaceTimer
from repro.roofline.hw import HW_V5E


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("shape_name", list(SHAPES))
def test_input_specs_all_cells(arch, shape_name):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        assert "sub-quadratic" in reason or "full" in reason
        return
    specs = input_specs(cfg, shape)
    assert specs["tokens"].dtype == jnp.int32
    B = shape.global_batch
    assert specs["tokens"].shape[0] == B
    if shape.kind == "decode":
        assert specs["tokens"].shape == (B, 1)
    if shape.kind == "train":
        assert specs["labels"].shape == specs["tokens"].shape
    if cfg.family == "vlm" and shape.kind != "decode":
        assert specs["patches"].shape == (B, cfg.num_patches,
                                          cfg.patch_embed_dim)
        assert specs["tokens"].shape[1] + cfg.num_patches == shape.seq_len
    if cfg.family == "encdec" and shape.kind != "decode":
        assert specs["frames"].shape == (B, cfg.encoder_seq, cfg.d_model)


def test_long_500k_applicability_matches_design():
    runnable = {a for a in ARCH_IDS
                if shape_applicable(get_config(a), SHAPES["long_500k"])[0]}
    assert runnable == {"falcon-mamba-7b", "recurrentgemma-2b",
                        "mixtral-8x7b"}


def test_decode_cache_len_divisible():
    for a in ARCH_IDS:
        for s in ("decode_32k", "long_500k"):
            n = decode_cache_len(get_config(a), SHAPES[s])
            assert n % 256 == 0 and n >= SHAPES[s].seq_len + 1


def test_model_flops_formulas():
    dense = get_config("granite-8b")
    moe = get_config("qwen3-moe-30b-a3b")
    tr = SHAPES["train_4k"]
    # train = 6 N D
    assert model_flops(dense, tr) == pytest.approx(
        6 * dense.param_count() * tr.global_batch * tr.seq_len)
    # MoE uses ACTIVE params only
    assert model_flops(moe, tr) < 6 * moe.param_count() * 1.05e6
    assert model_flops(moe, tr) == pytest.approx(
        6 * moe.param_count(active_only=True) * tr.global_batch * tr.seq_len)
    # decode: 2 N per token
    dec = SHAPES["decode_32k"]
    assert model_flops(dense, dec) == pytest.approx(
        2 * dense.param_count() * dec.global_batch)


def test_attention_pair_fraction():
    assert _attn_pair_fraction(4096, 0) == pytest.approx(0.5, abs=1e-3)
    # SWA: ~W/S for W << S
    f = _attn_pair_fraction(32768, 4096)
    assert 0.10 < f < 0.13
    # window >= S degenerates to causal-ish
    assert _attn_pair_fraction(128, 128) == pytest.approx(0.5, abs=0.01)


def test_attention_dense_flops_archs():
    swa, _ = attention_dense_flops(get_config("mixtral-8x7b"),
                                   SHAPES["prefill_32k"], "prefill")
    full, _ = attention_dense_flops(get_config("granite-8b"),
                                    SHAPES["prefill_32k"], "prefill")
    assert swa > 0 and full > 0
    d, skipped = attention_dense_flops(get_config("falcon-mamba-7b"),
                                       SHAPES["prefill_32k"], "prefill")
    assert d == 0 and skipped == 0          # attention-free


def test_interface_timer_and_dominants():
    t = InterfaceTimer(HW_V5E)
    assert t.compute(HW_V5E.peak_flops_bf16) == pytest.approx(1.0)
    assert t.memory(HW_V5E.hbm_bw) == pytest.approx(1.0)
    tl = Timeline(overlap=True)
    out = tl.simulate([{"compute_s": 0.1, "memory_s": 0.3,
                        "collective_s": 0.2}])
    assert out["dominant"] == "memory"
    assert out["total_s"] == pytest.approx(0.3)


def test_peaks_are_keyed_by_device_kind():
    """v5e's peaks come from its ``device_kind`` row; a CPU device has no
    roofline peaks, and a TPU kind missing from the table is an error."""
    import types

    from repro.roofline.capture import WindowCapture
    from repro.roofline.hw import device_peaks, peaks
    assert peaks("TPU v5 lite") is HW_V5E
    assert InterfaceTimer().hw is HW_V5E
    cpu = types.SimpleNamespace(platform="cpu", device_kind="cpu")
    assert device_peaks(cpu) is None
    v5e = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert device_peaks(v5e) is HW_V5E
    with pytest.raises(KeyError, match="TPU v9"):
        device_peaks(types.SimpleNamespace(platform="tpu",
                                           device_kind="TPU v9"))
    assert WindowCapture().hw is None       # these tests run on the CPU
    assert WindowCapture(hw=HW_V5E).hw is HW_V5E


def test_report_generation_from_records(tmp_path, monkeypatch):
    from repro.roofline import report as rep
    rec = {"arch": "granite-8b", "shape": "train_4k", "mesh": "16x16",
           "status": "ok", "compute_s": 1.0, "memory_s": 0.5,
           "memory_s_hlo": 1.5, "collective_s": 2.0,
           "dominant": "collective", "useful_ratio": 0.9,
           "roofline_fraction": 0.4, "roofline_fraction_kernel": 0.5,
           "step_time_bound_s": 2.0}
    roofs = {("granite-8b", "train_4k", "16x16"): rec}
    md = rep.roofline_section(roofs)
    assert "granite-8b" in md and "40.0%" in md and "50.0%" in md
    md2 = rep.timing_section(roofs)
    # core = max(C, M) = 1.0; serial = core + K = 3.0; overlap = max = 2.0
    assert "1.50x" in md2
