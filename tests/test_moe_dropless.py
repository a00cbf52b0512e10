"""Dropless expert routing on the path with no mesh: ``moe_apply``'s
``sort`` equals the dense all-experts oracle at any load, gradients
included, on ``ragged_dot`` and on the streaming kernel (interpret mode),
its stages carry the ``zp.moe.*`` scope names, the pair count picks the
kernel for decode and ``ragged_dot`` for a prefill, and the decode path
counts its routing into the ``moe_routing`` shell CSR that the farm
telemetry reports as ``moe.routing`` and ``moe.streamed``."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.core.pshell import drain, shell_init
from repro.farm.telemetry import FarmTelemetry
from repro.launch.serve import count_routing, decode_shell_config
from repro.models import build_model
from repro.models import moe as moe_mod
from repro.models import transformer as tfm
from repro.models.runtime import Runtime

jax.config.update("jax_platform_name", "cpu")


def _layer(skew: float):
    """A float32 smoke-size expert layer whose router sends ``skew`` more
    logit to expert 0, and 128 tokens for it."""
    cfg = dataclasses.replace(get_smoke_config("qwen3-moe-30b-a3b"),
                              dtype="float32")
    p = jax.tree.map(lambda a: a.astype(jnp.float32),
                     moe_mod.init_moe(jax.random.key(0), cfg))
    p["router"]["w"] = p["router"]["w"].at[:, 0].add(skew)
    x = jax.random.normal(jax.random.key(1), (4, 32, cfg.d_model),
                          jnp.float32)
    return cfg, p, x


@pytest.mark.parametrize("skew", [0.0, 3.0])
def test_dropless_equals_dense_at_any_load(skew):
    cfg, p, x = _layer(skew)
    y_dense, st_dense = moe_mod.moe_apply(p, cfg, x, impl="dense")
    y, st = moe_mod.moe_apply(p, cfg, x, impl="sort")
    np.testing.assert_allclose(y, y_dense, rtol=1e-5, atol=1e-5)
    assert float(st["dropped_frac"]) == 0.0
    for k in ("expert_toggles", "load"):
        np.testing.assert_array_equal(st[k], st_dense[k])
    if skew:
        # the capacity dispatch this path replaced drops tokens here
        x2 = x.reshape(-1, cfg.d_model)
        largest = float(jnp.max(st["load"])) * x2.shape[0] \
            * cfg.num_experts_per_tok
        assert largest > moe_mod._capacity(cfg, x2.shape[0],
                                           cfg.num_experts)
        assert float(moe_mod._moe_sort(p, cfg, x2)[1]["dropped_frac"]) > 0


def test_dropless_gradients_equal_dense():
    cfg, p, x = _layer(3.0)

    def loss(impl):
        return lambda p, x: jnp.sum(jnp.sin(
            moe_mod.moe_apply(p, cfg, x, impl=impl)[0]))
    g_dense = jax.grad(loss("dense"), argnums=(0, 1))(p, x)
    g = jax.grad(loss("sort"), argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_dense)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_expert_layer_stages_are_named_in_the_program():
    cfg, p, x = _layer(0.0)
    text = jax.jit(lambda p, x: moe_mod.moe_apply(p, cfg, x)[0]).lower(
        p, x).compile().as_text()
    for stage in ("route", "dispatch", "experts", "combine"):
        assert f"zp.moe.{stage}" in text


def test_routing_counts_a_hand_built_routing():
    # 4 tokens, top-2 over 8 experts: pairs on experts 0,0,0,1,1,5,5,5
    idx = jnp.array([[0, 1], [0, 5], [0, 5], [1, 5]])
    counts = jnp.bincount(idx.reshape(-1), length=8).astype(jnp.float32)
    stats = {"expert_toggles": counts > 0, "load": counts / 8.0}
    np.testing.assert_array_equal(moe_mod.routing_counts(stats, 8),
                                  [8, 3, 3, 0])
    np.testing.assert_array_equal(
        moe_mod.routing_counts({**stats, "streamed": jnp.int32(1)}, 8),
        [8, 3, 3, 1])
    rows = jnp.array([[8, 3, 3, 1], [8, 6, 2, 1], [8, 4, 4, 0]], jnp.int32)
    np.testing.assert_array_equal(tfm.fold_routing(rows), [24, 13, 4, 2])
    sh = shell_init(decode_shell_config(4))
    for r in rows:
        sh = count_routing(sh, r)
    records, _ = drain(sh)
    # the routing CSR's first four fields are as before the streamed count
    np.testing.assert_array_equal(records["csrs"]["moe_routing"],
                                  [3, 24, 13, 4, 2])
    tel = FarmTelemetry()
    tel.moe_routing("a", records)
    tel.moe_routing("b", {"csrs": {"moe_routing": np.array(
        [[1, 8, 2, 6, 1], [2, 16, 5, 1, 2]])}})    # a lane run: one row each
    tel.moe_routing("c", {"csrs": {"tokens": np.int32(3)}})
    assert tel.report()["moe"] == {
        "routing": {"steps": 6, "pairs": 48, "touched": 20, "largest": 6},
        "streamed": 5}


def test_decode_step_counts_the_experts_its_tokens_touch():
    cfg = get_smoke_config("qwen3-moe-30b-a3b")
    model = build_model(cfg, Runtime())
    params = model.init(jax.random.key(0))
    B = 3
    cache, _ = model.prefill(params, {"tokens": jnp.zeros((B, 8),
                                                          jnp.int32)}, 16)
    tok = jnp.array([[1], [2], [3]], jnp.int32)
    cache2, logits, routing = model.decode_step_routed(params, cache, tok)
    _, logits_plain = model.decode_step(params, cache, tok)
    np.testing.assert_array_equal(logits, logits_plain)
    pairs, touched, largest, streamed = (int(v) for v in routing)
    k, L = cfg.num_experts_per_tok, cfg.num_layers
    assert pairs == L * B * k
    assert L <= touched <= L * min(B * k, cfg.num_experts)
    assert 1 <= largest <= B
    assert streamed == 0                    # not a TPU: ragged_dot
    dense = build_model(get_smoke_config("glm4-9b"), Runtime())
    dp = dense.init(jax.random.key(0))
    dc, _ = dense.prefill(dp, {"tokens": jnp.zeros((B, 8), jnp.int32)}, 16)
    np.testing.assert_array_equal(dense.decode_step_routed(dp, dc, tok)[2],
                                  [0, 0, 0, 0])


# ------------------------------------------------- the streaming kernel ---
def _stream_on_cpu(monkeypatch):
    """Take the streaming branch on the CPU too, the kernel in interpret
    mode."""
    monkeypatch.setattr(moe_mod, "_grouped_ffn", functools.partial(
        moe_mod._ffn_streamed, interpret=True))


@pytest.fixture
def streamed_on_cpu(monkeypatch):
    _stream_on_cpu(monkeypatch)


@pytest.mark.parametrize("layer", [0, 2])
def test_streamed_layer_of_a_stack_equals_dense(layer, streamed_on_cpu):
    """Decode's few pairs (4 tokens x top-2) on the kernel path, the layer
    read in place from a 3-layer stack, first and last layer."""
    cfg, p, x = _layer(3.0)
    x = x[:, :1]
    y_dense, st_dense = moe_mod.moe_apply(p, cfg, x, impl="dense")
    # the other layers hold other weights
    stack = {**p, **{n: jnp.stack([p[n] * (i + 2) for i in range(3)])
                     .at[layer].set(p[n]) for n in ("gate", "up", "down")}}
    y, st = moe_mod.moe_apply(stack, cfg, x, impl="sort", layer=layer)
    assert int(st["streamed"]) == 1
    np.testing.assert_allclose(y, y_dense, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(st["expert_toggles"],
                                  st_dense["expert_toggles"])


def test_streamed_gradients_equal_dense(streamed_on_cpu):
    cfg, p, x = _layer(3.0)
    x = x[:, :2]

    def loss(impl):
        return lambda p, x: jnp.sum(jnp.sin(
            moe_mod.moe_apply(p, cfg, x, impl=impl)[0]))
    g_dense = jax.grad(loss("dense"), argnums=(0, 1))(p, x)
    g = jax.grad(loss("sort"), argnums=(0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_dense)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("tokens,streams", [(8, True), (8 * 2048, False)])
def test_pair_count_picks_the_products(tokens, streams):
    """At the qwen3-moe cell's widths: a decode step's 8 x top-8 = 64
    pairs stage the streaming kernel (for a TPU; ``ragged_dot``
    elsewhere), a prefill's 16384 x 8 = 131072 pairs only ``ragged_dot``.
    Traced from shapes alone."""
    cfg = get_config("qwen3-moe-30b-a3b")
    p = jax.eval_shape(lambda: moe_mod.init_moe(jax.random.key(0), cfg))
    x = jax.ShapeDtypeStruct((tokens, 1, cfg.d_model), jnp.bfloat16)
    pairs = tokens * cfg.num_experts_per_tok
    assert (pairs <= moe_mod.STREAM_MAX_PAIRS) == streams
    text = str(jax.make_jaxpr(
        lambda p, x: moe_mod.moe_apply(p, cfg, x)[1]["streamed"])(p, x))
    assert ("pallas_call" in text) == streams
    assert ("platform_index" in text) == streams
    assert "ragged_dot" in text


def test_decode_step_streams_every_layer(monkeypatch):
    """The decode step's routing counts one streamed layer-step per MoE
    layer, and its logits are the ``ragged_dot`` step's."""
    cfg = dataclasses.replace(get_smoke_config("qwen3-moe-30b-a3b"),
                              dtype="float32")
    model = build_model(cfg, Runtime())
    params = model.init(jax.random.key(0))
    B = 3
    cache, _ = model.prefill(params, {"tokens": jnp.zeros((B, 8),
                                                          jnp.int32)}, 16)
    tok = jnp.array([[1], [2], [3]], jnp.int32)
    plain_ffn = moe_mod._grouped_ffn
    _stream_on_cpu(monkeypatch)
    _, logits, routing = model.decode_step_routed(params, cache, tok)
    assert int(routing[3]) == cfg.num_layers
    monkeypatch.setattr(moe_mod, "_grouped_ffn", plain_ffn)
    _, logits_plain, plain = model.decode_step_routed(params, cache, tok)
    assert int(plain[3]) == 0
    np.testing.assert_array_equal(routing[:3], plain[:3])
    np.testing.assert_allclose(logits, logits_plain, rtol=1e-4, atol=1e-4)
