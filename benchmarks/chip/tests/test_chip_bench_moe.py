"""The sparse-expert decode cell (``qwen3-moe-30b-a3b.moe-decode``) at
smoke size on the CPU: the program's prefill then decode through the
cache agrees with the plain reference's full forward (``reference_moe``),
and a dropped routed expert does not; through the harness a sound run
comes out correct while the float8 control and an altered token do not;
the counts of ``flops_moe`` by hand; the per-layer readers, and their
silence on a program without the routing counter."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import smoke
from chip import flops_moe, harness, reference_moe, weights_moe

WORKLOAD = "qwen3-moe-30b-a3b.moe-decode"
#: float32 at smoke size: at width 64 bfloat16 rounding flips as many
#: experts as the float8 control does
SMOKE_CONFIG = dict(num_hidden_layers=2, hidden_size=64,
                    moe_intermediate_size=96, num_experts=8,
                    num_experts_per_tok=2, num_attention_heads=4,
                    num_key_value_heads=2, head_dim=16, vocab_size=256,
                    torch_dtype="float32")
SMOKE_MIX = dict(batch=2, prompt=32, gen=17, window_tokens=4, max_boards=400,
                 check_boards=4)
#: smoke-size CPU readings (4 seeds): the program 0 on each, the control
#: 0.0093-0.0225
SMOKE_LIMIT = 0.001
SEED = 2 ** 31 + 11


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = smoke.tree(tmp_path_factory.mktemp("bench"))
    here = root / "benchmarks" / "chip"
    smoke._patch(here / "configs" / "qwen3-moe-30b-a3b.json", **SMOKE_CONFIG)
    smoke._patch(here / "mixes" / "moe-decode.json", **SMOKE_MIX)
    (here / "limits" / f"{WORKLOAD}.json").write_text(json.dumps(
        {"numbers": {"logit_gap_mean": {"limit": SMOKE_LIMIT}}}))
    return root


def _spec():
    doc = json.loads((smoke.CHIP / "configs"
                      / "qwen3-moe-30b-a3b.json").read_text())
    doc.update(SMOKE_CONFIG)
    return flops_moe.moe_spec(doc)


def _program_and_reference_logits(drop_expert=None):
    """Prefill 12 tokens, decode 6 through the cache (float32 weights),
    and the reference's logits for the same positions."""
    kind = harness.kind_module("moe_decode")
    spec = _spec()
    from repro.models import build_model
    from repro.models.runtime import Runtime
    model = build_model(kind.program_config(spec), Runtime())
    canon = weights_moe.make_weights(spec, SEED)
    params = weights_moe.to_program(canon, model)
    if drop_expert is not None:
        moe = params["stack"]["blocks"][0]["moe"]
        moe["down"] = moe["down"].at[:, drop_expert].set(0.0)
    B, P, G = 2, 12, 6
    prompt = np.random.default_rng(0).integers(0, spec.vocab, (B, P),
                                               dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        cache, logits = model.prefill(params, {"tokens": jnp.asarray(prompt)},
                                      P + G + 1)
        got = [logits[:, -1]]
        toks = [jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]]
        for _ in range(G - 1):
            cache, logits = model.decode_step(params, cache, toks[-1])
            got.append(logits[:, -1])
            toks.append(jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None])
        seq = jnp.concatenate([jnp.asarray(prompt)] + toks, axis=1)
        ref = reference_moe.next_token_logits(canon, seq, spec=spec,
                                              start=P - 1)
    return np.asarray(jnp.stack(got, axis=1)), np.asarray(ref)


def test_program_prefill_and_decode_match_the_reference():
    got, ref = _program_and_reference_logits()
    assert got.shape == ref.shape
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


def test_a_dropped_expert_does_not_match_the_reference():
    got, ref = _program_and_reference_logits(drop_expert=3)
    assert np.max(np.abs(got - ref)) > 100 * 1e-4


def test_combine_weights_keep_the_top_k_renormalised():
    probs = jnp.array([[0.1, 0.4, 0.2, 0.3], [0.25, 0.05, 0.6, 0.1]])
    c = np.asarray(reference_moe.combine_weights(probs, 2))
    np.testing.assert_allclose(c, [[0, 4 / 7, 0, 3 / 7],
                                   [0.25 / 0.85, 0, 0.6 / 0.85, 0]],
                               rtol=1e-6)


def _values(out):
    return {k: v["value"] for k, v in out["check"].items()}


def test_sound_run_control_and_fault(root):
    got = {}
    sound = smoke.run(root, WORKLOAD, seed=SEED,
                      after=lambda kind: got.update(kind.control()))
    assert sound["correct"] is True, sound["check"]
    routing = sound["detail"]["moe_routing"]
    spec = _spec()
    assert routing["pairs"] == routing["steps"] * spec.layers \
        * SMOKE_MIX["batch"] * spec.top_k
    assert routing["steps"] * spec.layers <= routing["touched"]
    prog = _values(sound)
    assert got["logit_gap_mean"] >= 3 * prog["logit_gap_mean"]
    assert got["logit_gap_mean"] > SMOKE_LIMIT

    ctl = smoke.run(root, WORKLOAD, seed=SEED, control=True)
    assert ctl["correct"] is False, ctl["check"]
    bad = smoke.run(root, WORKLOAD, seed=SEED, fault="token_altered")
    assert bad["correct"] is False
    assert bad["check"]["logit_gap_mean"]["value"] >= 10 * max(
        prog["logit_gap_mean"], SMOKE_LIMIT)


def test_flops_moe_hand_counts():
    spec = flops_moe.MoeSpec(name="t", layers=2, d_model=4, heads=2,
                             kv_heads=1, head_dim=2, experts=4, top_k=2,
                             expert_ff=3, vocab=10, rope_theta=1e6,
                             norm_eps=1e-6, dtype="bfloat16")
    assert flops_moe.attention_params(spec) == 16 + 16 + 16
    assert flops_moe.router_params(spec) == 16
    assert flops_moe.expert_params(spec) == 36
    assert flops_moe.active_layer_params(spec) == 48 + 16 + 72
    # 2 layers x (2 x 3 tokens x 136 + 4 x 2 heads x 2 x 6 pairs) + head
    assert flops_moe.prefill_flops(spec, 1, 3) == 2 * (816 + 96) + 80
    # 2 x 2 rows x (2 x 136 + 40) + 2 layers x 4 x 2 x 2 x 2 x 5 positions
    assert flops_moe.decode_token_flops(spec, 2, 4) == 1248 + 320
    # per layer (48 + 3 touched x 36) x 2 + router 16 x 4; head 80;
    # embedding rows 16; KV 32 a position, 5 read and one written
    assert flops_moe.decode_token_bytes(spec, 2, 4, 3) == 752 + 80 + 16 + 192


def _record(routing):
    spec = _spec()
    mix = json.loads((smoke.CHIP / "mixes" / "moe-decode.json").read_text())
    return {"trace": {"modules": {"jit_engine": 2.0, "jit_prefill_step": 0.5,
                                  "jit_copy": 0.1},
                      "module_counts": {"jit_engine": 100.0},
                      "busy_s": 2.5},
            "peak": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
            "moe_spec": spec, "moe_routing": routing, "rate": 1000.0,
            "mix": mix}


def test_per_layer_readers():
    read = {m: harness.metric_reader(m, smoke.CHIP) for m in (
        "hbm_roofline.moe_decode", "mfu.moe_decode",
        "prefill_share.moe_decode")}
    spec = _spec()
    rec = _record({"steps": 10, "pairs": 10 * spec.layers * 16,
                   "touched": 10 * spec.layers * 5, "largest": 3})
    pos = 2048 + 257 // 2
    need = 800 * flops_moe.decode_token_bytes(spec, 8, pos, 5.0)
    assert read["hbm_roofline.moe_decode"](rec) == pytest.approx(
        100 * need / 819e9 / 2.0)
    assert read["mfu.moe_decode"](rec) == pytest.approx(
        100 * flops_moe.decode_token_flops(spec, 8, pos) / 8 * 1000 / 197e12)
    assert read["prefill_share.moe_decode"](rec) == pytest.approx(20.0)
    # a program without the routing counter: the roofline stays silent
    assert read["hbm_roofline.moe_decode"](_record(None)) is None
    assert read["prefill_share.moe_decode"]({"trace": None}) is None
