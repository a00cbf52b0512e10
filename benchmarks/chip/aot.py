"""Compile the programs a cell's window runs for a described TPU v5e, with
no chip attached, and print each one's ``memory_analysis`` as JSON lines:

  JAX_PLATFORMS=cpu python3 benchmarks/chip/aot.py <workload> [...]

A rehearsal before a chip run: it shows whether the programs fit one
chip's memory, and what they hold. It runs nothing and measures no time.
"""
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[0] = os.path.dirname(HERE)
sys.path.insert(1, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from chip import harness  # noqa: E402


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "alias_size_in_bytes", "temp_size_in_bytes",
            "generated_code_size_in_bytes")
    return {k: int(getattr(m, k)) for k in keys}


def _on(tree, sharding):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        np.shape(a), a.dtype, sharding=sharding), tree)


def programs(cell, sharding):
    """(name, jitted fn, abstract args) of every program the window runs."""
    from repro.core.pshell import shell_init
    from repro.models import build_model
    from repro.models.runtime import Runtime
    cfg = harness.program_config(cell.spec)
    mix = cell.mix
    if cell.kind == "train":
        from repro.launch.farm import _train_board_parts
        from repro.train.step import init_state, make_train_step
        g = int(mix["window_steps"])
        parts = _train_board_parts(cfg, g, g, batch=int(mix["batch"]),
                                   seq=int(mix["seq"]), seed=0)
        model = build_model(cfg, Runtime(taps=frozenset({"commits"})))
        state = jax.eval_shape(lambda k: init_state(model, k),
                               jax.random.key(0))
        batch = harness.kind_module("train").train_batches(
            cell.spec, dict(mix, batches=1), 0)[0]
        stack = jax.tree.map(lambda a: np.stack([a] * g), batch)
        yield ("train_window", parts["engine"],
               _on((state, parts["shell"], stack), sharding))
        yield ("oracle_step", jax.jit(make_train_step(model)),
               _on((state, batch), sharding))
    elif cell.kind == "decode":
        from repro.launch.serve import decode_shell_config, make_decode_engine
        from repro.serve import make_prefill_step
        model = build_model(cfg, Runtime())
        B, P = int(mix["batch"]), int(mix["prompt"])
        gen, g = int(mix["gen"]), int(mix["window_tokens"])
        params = jax.eval_shape(model.init, jax.random.key(0))
        prefill = jax.jit(make_prefill_step(model, P + gen + 8))
        tokens = {"tokens": jax.ShapeDtypeStruct((B, P), jnp.int32)}
        yield "prefill", prefill, _on((params, tokens), sharding)
        cache, _ = jax.eval_shape(prefill, params, tokens)
        tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        shell = shell_init(decode_shell_config(g))
        idx = np.arange(g, dtype=np.int64)
        yield ("decode_window", make_decode_engine(model),
               _on(((params, cache, tok), shell, idx), sharding))


def main(argv):
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    for workload in argv:
        cell = harness.load_cell(workload)
        for name, fn, args in programs(cell, one):
            compiled = fn.lower(*args).compile()
            print(json.dumps({"workload": workload, "program": name,
                              **_mem(compiled)}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
