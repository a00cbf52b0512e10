"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

The TPU compiler is installed with jaxlib, so each Pallas kernel of the
main path is compiled here at a published model width for one chip of a
described ``v5e:2x2`` topology: a BlockSpec the chip's tiling refuses, or
a kernel that needs more VMEM than it may use, fails here instead of on
the chip. The shapes come from ``repro.kernels.cases``, the same table the
on-chip check in ``chip_smoke.py`` runs. Interpret-mode agreement lives in
``test_kernels.py``.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and under pytest-xdist
every worker imports this file.
"""
import collections
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.pshell import shell_init
from repro.kernels.cases import kernel_cases
from repro.launch.serve import decode_shell_config, make_decode_engine
from repro.models import build_model
from repro.models.runtime import Runtime


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


CASES = kernel_cases()


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    case = CASES[name]
    compiled = jax.jit(case.call).lower(*_on(one_chip, case.operands)) \
        .compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes < 16 * 2**30


_HLO_OP = re.compile(r"= (?:bf16|f32)\[([\d,]*)\]\{[^}]*\} ([a-z][\w-]*)\(")
# ops that write a new buffer (not a view, a slice or an in-place update)
_WRITES = {"copy", "copy-done", "transpose", "fusion", "pad"}


def _decode_window_cache_ops(impl, sharding, B=4, W=1024):
    """Compile the serving decode window (2 granite-8b layers, a W-slot
    cache) and count, by (op, shape), the ops that write a new buffer at
    least one layer's K cache in size."""
    cfg = dataclasses.replace(get_config("granite-8b"), num_layers=2)
    model = build_model(cfg, Runtime(attention_impl=impl))
    cache = model.cache_spec(B, W)
    state = (jax.eval_shape(model.init, jax.random.key(0)), cache,
             jax.ShapeDtypeStruct((B, 1), jnp.int32))
    shell = jax.eval_shape(lambda: shell_init(decode_shell_config(4)))
    idx = jax.ShapeDtypeStruct((4,), jnp.int32)
    text = make_decode_engine(model).lower(
        *_on(sharding, (state, shell, idx))).compile().as_text()
    layer_cache = B * W * cfg.num_kv_heads * cfg.head_dim
    big = collections.Counter()
    for dims, op in _HLO_OP.findall(text):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        if n >= layer_cache and op in _WRITES:
            big[(op, dims)] += 1
    return text, big


def test_pallas_decode_reads_the_carried_cache_in_place(one_chip):
    """The decode kernel wants the cache head-major; its wrapper's
    transpose must compile to a bitcast of the loop-carried cache (XLA
    keeps the carry head-major), so the Pallas decode window writes no
    more cache-sized buffers, of no other shape, than the XLA-attention
    window does."""
    text, pallas_ops = _decode_window_cache_ops("pallas", one_chip)
    assert "tpu_custom_call" in text
    _, xla_ops = _decode_window_cache_ops("xla", one_chip)
    assert pallas_ops, "no cache-sized op found: the count is not looking"
    extra = {k: n for k, n in pallas_ops.items() if n > xla_ops.get(k, 0)}
    assert not extra, extra
