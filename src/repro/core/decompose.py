"""Scale-Down decomposition (DESIGN C1): extract any block with its exact
interface, capture real boundary traffic from an in-situ run, replay the
extracted block standalone, and verify bit-identity.

This is the paper's central claim made executable: a subsystem prototyped
behind a preserved interface behaves exactly as in situ ("strict
non-interference of the DUT"). The roofline composer (repro.roofline.compose)
uses the same decomposition to extrapolate full-system cost from per-block
dry-runs — the Scale-Up/Scale-Down cycle of Fig. 1.

``coemu.verify_subsystems`` drives several extracted blocks as independent
DUT boards of one ZP-Farm pass (one slot thread per block) against the
captured boundary traffic — the multi-board farm shape.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer as tfm
from repro.models.runtime import Runtime


def iter_layer_params(params, cfg):
    """Yield (layer_idx, spec, per-layer param tree) from the stacked stack."""
    stack = params["stack"]
    P_len = len(cfg.layer_pattern)
    n_periods = cfg.num_layers // P_len
    for period in range(n_periods):
        for pos in range(P_len):
            tree = jax.tree.map(lambda a: a[period], stack["blocks"][pos])
            yield period * P_len + pos, cfg.layer_pattern[pos], tree
    for i, tree in enumerate(stack["tail"]):
        yield n_periods * P_len + i, cfg.layer_pattern[i % P_len], tree


@dataclasses.dataclass
class Subsystem:
    """An extracted block: pure fn + its interface specs + golden oracle.
    ``params`` is the block's own param slice — ``fn`` closes over it, and
    lane-batched callers pass it separately as board STATE so same-spec
    boards can share ONE parameterized engine."""
    name: str
    layer_idx: int
    spec: Tuple[str, Optional[str]]
    fn: Callable          # (x, positions) -> x'
    input_specs: Dict[str, jax.ShapeDtypeStruct]
    params: Any = None


def _block_subsystem(layer_idx: int, spec, tree, cfg, rt: Runtime,
                     batch: int, seq: int) -> Subsystem:
    def fn(x, positions):
        y, _ = tfm.block_apply(tree, cfg, spec, x, positions, rt)
        return y

    from repro.utils import dtype_of
    specs = {
        "x": jax.ShapeDtypeStruct((batch, seq, cfg.d_model),
                                  dtype_of(cfg.dtype)),
        "positions": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
    }
    return Subsystem(name=f"layer{layer_idx}:{spec[0]}+{spec[1]}",
                     layer_idx=layer_idx, spec=spec, fn=fn,
                     input_specs=specs, params=tree)


def extract_blocks(params, cfg, layer_idxs, rt: Runtime,
                   batch: int, seq: int) -> Dict[int, Subsystem]:
    """Single-walk multi-block extraction: ONE pass over
    ``iter_layer_params`` materializes exactly the requested layers'
    param slices. Per-board ``extract_block`` calls each re-walk the
    stacked params and materialize every earlier layer's slice along the
    way — O(boards x layers) slice materializations that
    ``subsystem_boards`` used to pay per farm build."""
    want = set(layer_idxs)
    bad = sorted(li for li in want if not 0 <= li < cfg.num_layers)
    if bad:
        # smoke archs are tiny (granite-8b and glm4-9b have 2 decoder
        # layers) — name the arch and its layer count instead of letting a
        # bare IndexError escape from the stacked-params walk
        raise ValueError(
            f"layer_idx {bad[0]} out of range for arch {cfg.name!r}: "
            f"{cfg.num_layers} decoder layers (valid: 0.."
            f"{cfg.num_layers - 1})")
    out = {}
    for idx, spec, tree in iter_layer_params(params, cfg):
        if idx in want:
            out[idx] = _block_subsystem(idx, spec, tree, cfg, rt,
                                        batch, seq)
            if len(out) == len(want):
                break
    return out


def extract_block(params, cfg, layer_idx: int, rt: Runtime,
                  batch: int, seq: int) -> Subsystem:
    return extract_blocks(params, cfg, [layer_idx], rt,
                          batch, seq)[layer_idx]


def unrolled_capture(params, cfg, x, positions, rt: Runtime):
    """In-situ run with boundary capture: returns the list of (x_in, x_out)
    at every block boundary (smoke-scale only — full activations)."""
    records = []
    for idx, spec, tree in iter_layer_params(params, cfg):
        x_in = x
        x, _ = tfm.block_apply(tree, cfg, spec, x, positions, rt)
        records.append({"layer": idx, "x_in": x_in, "x_out": x})
    return x, records


def verify_extraction(params, cfg, batch_x, positions, rt: Runtime,
                      layer_idx: int) -> Dict[str, Any]:
    """Capture in-situ traffic, replay the extracted block standalone,
    assert BITWISE equality (the non-interference contract)."""
    _, records = unrolled_capture(params, cfg, batch_x, positions, rt)
    rec = records[layer_idx]
    sub = extract_block(params, cfg, layer_idx, rt,
                        batch_x.shape[0], batch_x.shape[1])
    replay = sub.fn(rec["x_in"], positions)
    bitwise = np.array_equal(np.asarray(replay), np.asarray(rec["x_out"]))
    max_abs = float(np.max(np.abs(
        np.asarray(replay, np.float32) - np.asarray(rec["x_out"],
                                                    np.float32))))
    return {"subsystem": sub.name, "bitwise_identical": bool(bitwise),
            "max_abs_diff": max_abs}


def scanned_vs_unrolled(params, cfg, x, positions, rt: Runtime):
    """The production forward (scan-over-periods) vs the unrolled composition
    of extracted blocks: the Scale-Up model vs composed Scale-Down parts."""
    x_scan, _ = tfm.stack_apply(params["stack"], cfg, x, positions, rt)
    x_unroll, _ = unrolled_capture(params, cfg, x, positions, rt)
    a = np.asarray(x_scan, np.float32)
    b = np.asarray(x_unroll, np.float32)
    return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-6))
