"""The benchmark's operation counts (chip/flops.py) against XLA's
``cost_analysis`` of the program's own smoke-size programs, on the CPU.

The models are built with one layer: XLA counts the body of a scanned
layer stack once, whatever its trip count. ``flops.py`` counts matrix
products only, and attention here as the full masked square the program
computes (``causal=False``), so XLA's count is the larger by the
elementwise work (norms, rotary positions, softmax, SiLU, the loss):
at width 64 that is under 20% of a forward or train step and of one
layer, and under 40% of a one-token decode step, whose matrices are
tiny. The count may never exceed XLA's: a share of a peak built on it
would then overstate the work.
"""
import json

import jax
import jax.numpy as jnp
import pytest

import smoke
from chip import flops, harness

CASES = ["internvl2-1b", "granite-8b"]


def _spec(name):
    doc = json.loads((smoke.CHIP / "configs" / f"{name}.json").read_text())
    doc.update(smoke.SMOKE_CONFIGS[name], num_hidden_layers=1)
    return harness.model_spec(doc)


def _model(spec):
    from repro.models import build_model
    from repro.models.runtime import Runtime
    cfg = harness.program_config(spec)
    model = build_model(cfg, Runtime())
    return cfg, model, jax.eval_shape(model.init, jax.random.key(0))


def _xla(fn, *args):
    return jax.jit(fn).lower(*args).compile().cost_analysis()["flops"]


def _batch(spec, B, S):
    T = S - spec.patches
    b = {"tokens": jax.ShapeDtypeStruct((B, T), jnp.int32),
         "labels": jax.ShapeDtypeStruct((B, T), jnp.int32)}
    if spec.patches:
        b["patches"] = jax.ShapeDtypeStruct(
            (B, spec.patches, spec.patch_dim), jnp.bfloat16)
    return b


@pytest.mark.parametrize("name", CASES)
def test_forward_and_train_step_counts(name):
    spec = _spec(name)
    _, model, params = _model(spec)
    B, S = 2, 64
    batch = _batch(spec, B, S)
    xla_fwd = _xla(lambda p, b: model.logits(p, b)[0], params, batch)
    ours = flops.forward_flops(spec, B, S, S, causal=False)
    assert 0.8 <= ours / xla_fwd <= 1.0
    xla_train = _xla(jax.grad(lambda p, b: model.loss(p, b)[0]),
                     params, batch)
    ours = flops.train_step_flops(spec, B, S, causal=False)
    assert 0.8 <= ours / xla_train <= 1.0


@pytest.mark.parametrize("name", CASES)
def test_layer_and_decode_counts(name):
    from repro.models import transformer as tfm
    from repro.models.runtime import Runtime
    spec = _spec(name)
    cfg, model, params = _model(spec)
    B, S = 2, 64
    lp = jax.eval_shape(lambda p: jax.tree.map(
        lambda a: a[0], p["stack"]["blocks"][0]), params)
    x = jax.ShapeDtypeStruct((B, S, spec.d_model), jnp.bfloat16)
    pos = jax.ShapeDtypeStruct((B, S), jnp.int32)
    xla = _xla(lambda lp, x, pos: tfm.block_apply(
        lp, cfg, ("attn", "mlp"), x, pos, Runtime())[0], lp, x, pos)
    assert 0.8 <= flops.layer_forward_flops(spec, B, S, causal=False) \
        / xla <= 1.0
    cache = model.cache_spec(B, 128)
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    xla = _xla(model.decode_step, params, cache, tok)
    # the step attends over the whole 128-slot cache: position 127
    assert 0.6 <= flops.decode_token_flops(spec, B, 127) / xla <= 1.0


def test_causal_count_is_below_the_square():
    spec = _spec("granite-8b")
    full = flops.attention_flops(spec, 1, 512, causal=False)
    half = flops.attention_flops(spec, 1, 512, causal=True)
    assert half < full and half == full * 513 // 1024


def test_decode_bytes_cover_weights_and_cache():
    spec = _spec("granite-8b")
    w = (flops.layer_matmul_params(spec) + spec.d_model * spec.vocab) * 2
    b0 = flops.decode_token_bytes(spec, 4, 0)
    b1 = flops.decode_token_bytes(spec, 4, 1)
    per_pos = 2 * spec.layers * 4 * spec.kv_heads * spec.head_dim * 2
    assert b0 > w and b1 - b0 == per_pos
