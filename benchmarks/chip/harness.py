"""The benchmark's general harness: finds a cell's configuration, traffic
mix, limits and per-layer metric readers by the names in
``BENCHMARK.json``, drives the program's farm for a fixed window, checks
what the window produced against the plain reference, and assembles the
result line.

Nothing here names a cell. A cell is a ``workloads`` entry; its
configuration is ``configs/<config>.json``, its traffic
``mixes/<traffic>.json``, whose ``kind`` names the generator
``kinds/<kind>.py`` (see ``cells.py``), its correctness limits
``limits/<workload>.json``, and each per-layer metric
``metrics/<name>.py``, or ``metrics/<stem>.py`` for the part of the
name before its first dot (a ``read(record)`` function that returns a
number, or ``None`` when it finds nothing).
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import sys
import threading
import time
from collections import defaultdict, deque
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


class HarnessError(RuntimeError):
    """A run that cannot produce a result (exit non-zero, no line)."""


# ---------------------------------------------------------------- loading --
def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest(root: Path = ROOT) -> dict:
    return load_json(Path(root) / "BENCHMARK.json")


def cell_entry(man: dict, workload: str) -> dict:
    for w in man["workloads"]:
        if w["name"] == workload:
            return w
    raise HarnessError(f"no workload {workload!r} in BENCHMARK.json")


def config_entry(man: dict, name: str) -> dict:
    for c in man["configs"]:
        if c["name"] == name:
            return c
    raise HarnessError(f"no config {name!r} in BENCHMARK.json")


def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_path(name: str, here: Path = HERE) -> Path:
    """``metrics/<name>.py``, else ``metrics/<stem>.py`` where the stem is
    the name before its first dot (one reader for ``device_idle.train``
    and ``device_idle.decode`` alike)."""
    path = here / "metrics" / f"{name}.py"
    if not path.is_file():
        path = here / "metrics" / f"{name.split('.')[0]}.py"
    return path


def metric_reader(name: str, here: Path = HERE):
    mod = _module(metric_path(name, here),
                  f"chip_metric_{name.replace('.', '_').replace('-', '_')}")
    return mod.read


def kind_module(kind: str, here: Path = HERE):
    """The generator of a traffic kind: the module ``kinds/<kind>.py``."""
    path = here / "kinds" / f"{kind}.py"
    if not path.is_file():
        raise HarnessError(f"no traffic kind {kind!r} ({path.name})")
    return _module(path, f"chip_kind_{kind.replace('-', '_')}")


def kind_class(kind: str, here: Path = HERE):
    return kind_module(kind, here).Kind


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    """A dense decoder's shapes, read from a configuration file."""
    name: str
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float
    tie_embeddings: bool
    dtype: str
    patches: int = 0
    patch_dim: int = 0


def model_spec(doc: dict) -> ModelSpec:
    return ModelSpec(
        name=doc["name"], layers=int(doc["num_hidden_layers"]),
        d_model=int(doc["hidden_size"]),
        heads=int(doc["num_attention_heads"]),
        kv_heads=int(doc["num_key_value_heads"]),
        head_dim=int(doc["head_dim"]),
        d_ff=int(doc["intermediate_size"]), vocab=int(doc["vocab_size"]),
        rope_theta=float(doc["rope_theta"]),
        norm_eps=float(doc["rms_norm_eps"]),
        tie_embeddings=bool(doc["tie_word_embeddings"]),
        dtype=str(doc["torch_dtype"]),
        patches=int(doc.get("num_image_token", 0)),
        patch_dim=int(doc.get("vit_hidden_size", 0)))


def program_config(spec: ModelSpec):
    """The program's ``ModelConfig`` for ``spec`` (the system under test)."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=spec.name, family="vlm" if spec.patches else "dense",
        num_layers=spec.layers, d_model=spec.d_model,
        num_heads=spec.heads, num_kv_heads=spec.kv_heads,
        head_dim=spec.head_dim, d_ff=spec.d_ff, vocab_size=spec.vocab,
        num_patches=spec.patches, patch_embed_dim=spec.patch_dim,
        rope_theta=spec.rope_theta, norm_eps=spec.norm_eps,
        tie_embeddings=spec.tie_embeddings, dtype=spec.dtype)


@dataclasses.dataclass
class Cell:
    """Everything one run of one workload needs."""
    name: str
    entry: dict
    config: dict
    mix: dict
    limits: dict
    man: dict
    here: Path = HERE
    root: Path = ROOT

    @property
    def kind(self) -> str:
        return self.mix["kind"]

    @property
    def spec(self) -> ModelSpec:
        """The configuration's shapes, for kinds that run a dense decoder
        (a kind for another family reads ``config`` itself)."""
        return model_spec(self.config)


def load_cell(workload: str, root: Path = ROOT, here: Path = HERE,
              man: dict = None) -> Cell:
    man = man or manifest(root)
    entry = cell_entry(man, workload)
    centry = config_entry(man, entry["config"])
    doc = load_json(Path(root) / centry["file"])
    mix = load_json(here / "mixes" / f"{entry['traffic']}.json")
    lim_path = here / "limits" / f"{workload}.json"
    limits = load_json(lim_path) if lim_path.exists() else {}
    return Cell(name=workload, entry=entry, config=doc, mix=mix,
                limits=limits, man=man, here=here, root=Path(root))


# --------------------------------------------------------------- recorder --
@dataclasses.dataclass
class Row:
    """One drained window: dispatch and verified stamps (host clock),
    units of work, whether the farm's own verdict failed it, and what the
    benchmark keeps to check it against the reference."""
    job: str
    index: int
    t0: float
    t1: float
    units: int
    failed: bool
    payload: object = None


class Recorder:
    """The benchmark's hooks on a farm job: the engine wrapper stamps the
    dispatch, the ``verify`` wrapper stamps the verified drain. Both open
    ``bench.*`` host spans for the trace. Windows of one job dispatch and
    drain in order, so dispatch stamps match drains first in, first out."""

    def __init__(self):
        self.rows: list = []
        self._t0 = defaultdict(deque)

    def engine(self, job: str, engine):
        from jax.profiler import TraceAnnotation
        t0s = self._t0[job]

        def wrapped(state, shell, stack):
            with TraceAnnotation("bench.dispatch"):
                t0s.append(time.perf_counter())
                return engine(state, shell, stack)
        return wrapped

    def verify(self, job: str, check):
        """``check(plan, records, ys) -> (units, failed, payload)``; an
        exception from it is the farm's veto: recorded failed, re-raised."""
        from jax.profiler import TraceAnnotation
        t0s = self._t0[job]

        def verify(plan, records, ys):
            with TraceAnnotation("bench.verify"):
                try:
                    units, failed, payload = check(plan, records, ys)
                    exc = None
                except Exception as e:  # noqa: BLE001 — the farm's veto
                    units, failed, payload, exc = plan.size, True, None, e
                t0 = t0s.popleft() if t0s else float("nan")
                self.rows.append(Row(job, plan.index, t0,
                                     time.perf_counter(), units, failed,
                                     payload))
                if exc is not None:
                    raise exc
        return verify

    @staticmethod
    def drain(fn):
        from jax.profiler import TraceAnnotation

        def drain(snap):
            with TraceAnnotation("bench.drain"):
                return fn(snap)
        return drain


def release(mgr, job: str):
    """An ``on_drain`` sink that drops a finished board's final state from
    the farm's ``results`` (a long campaign would otherwise keep every
    board's state on the device)."""
    def sink(plan, records, ys):
        mgr.results.pop(job, None)
        mgr.outputs.pop(job, None)
    return sink


# ------------------------------------------------------------------ window --
def timed_farm(mgr, seconds: float, trace_dir: str = None):
    """``mgr.run`` for ``seconds``, then ``request_shutdown``: running
    boards are cut at their next drain. Returns (report, t_start, t_end,
    t_return). The profiler, when asked for, runs around the window."""
    import jax
    from jax.profiler import TraceAnnotation
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    timer = threading.Timer(seconds, mgr.request_shutdown)
    t_start = time.perf_counter()
    timer.start()
    try:
        with TraceAnnotation("bench.window"):
            report = mgr.run(strict=False)
    finally:
        timer.cancel()
        timer.join()
        t_ret = time.perf_counter()
        if trace_dir:
            jax.profiler.stop_trace()
    t_end = t_start + seconds
    if t_ret < t_end:
        failed = {n: j["error"] for n, j in report["jobs"].items()
                  if j["status"] not in ("done", "interrupted")}
        raise HarnessError(
            f"the mix ran out of work {t_end - t_ret:.3f} s before the "
            f"window closed; failed boards: {failed}"[:2000])
    return report, t_start, t_end, t_ret


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


class CompileCounter:
    """Counts backend compilations (JAX's monitoring events) while on."""

    def __init__(self):
        import jax.monitoring as mon
        self.n = 0
        self.on = False

        def listener(event, duration, **kw):
            if self.on and "backend_compile" in event:
                self.n += 1
        mon.register_event_duration_secs_listener(listener)


# --------------------------------------------------------------------- run --
def run(cell: Cell, seed: int, seconds: float, trace: bool, *,
        t_process: float, require_tpu: bool = True, fault=None,
        control: bool = False, after=None) -> dict:
    """One run of ``cell``: set-up, the timed window, the reference check.
    Returns the result line as a dict (``check`` last). ``control``
    puts the control (the reference in a lower precision) in the
    program's place for the numbers compared. ``after(kind)``, if given,
    runs once the check is done (calibration reads the control there, on
    the same run's inputs)."""
    import jax
    from chip.peaks import peaks

    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise HarnessError(f"needs a TPU; JAX found {dev.platform!r}")
    chips = int(cell.entry["chips"])
    if len(devices) < chips:
        raise HarnessError(f"{cell.name} needs {chips} chips; JAX found "
                           f"{len(devices)}")
    used = devices[:chips]
    peak = peaks(dev.device_kind) if require_tpu else None

    counter = CompileCounter()
    kind = kind_class(cell.kind, cell.here)(cell, seed, fault=fault)
    kind.setup()
    rec = Recorder()
    mgr = kind.farm(rec)
    trace_dir = None
    if trace:
        trace_dir = str(cell.root / ".bench_traces" / cell.name)
        _rmtree(trace_dir)
    gc.collect()
    counter.on = True
    report, t_start, t_end, t_ret = timed_farm(mgr, seconds, trace_dir)
    counter.on = False
    setup_s = t_start - t_process

    try:
        record = kind.account(rec, report, mgr, t_start, t_end)
    except HarnessError as e:
        raise HarnessError(f"{e} ({counter.n} compiles in the window, "
                           f"jobs {report['jobs']})"[:2000]) from None
    record.update(seconds=seconds, setup_s=setup_s, spec=cell.spec,
                  mix=cell.mix, peak=peak, compiles_in_window=counter.n)
    mem = peak_bytes(used)
    trace_red = None
    if trace:
        from chip import trace as tr
        try:
            trace_red = tr.reduce(tr.load(tr.find_xplane(trace_dir)))
            record["trace"] = trace_red
        except ValueError:
            if require_tpu:     # no device plane: nothing ran on a chip
                raise
        _rmtree(trace_dir)
    del mgr, report
    kind.release()
    gc.collect()

    numbers = kind.control() if control else kind.check(rec)
    if after is not None:
        after(kind)
    limits = cell.limits.get("numbers", {})
    check = {}
    for name, value in numbers.items():
        lim = limits.get(name, {}).get("limit")
        check[name] = {"value": value, "limit": lim}
    correct = bool(check) and all(
        c["limit"] is not None and c["value"] is not None
        and c["value"] <= c["limit"] for c in check.values())

    metrics = {}
    if trace:
        for m in cell.man["per_layer"]:
            if not _reports(m, cell, cell.man):
                continue
            v = metric_reader(m["name"], cell.here)(record)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = {"setup_s": setup_s, "window_p95_ms": record["window_p95_ms"],
               kind.rate_metric: record["rate"]}
        for m in cell.man["end_to_end"]:
            if "workloads" in m and cell.name not in m["workloads"]:
                continue
            if m["name"] not in e2e:
                raise HarnessError(f"{cell.name} does not measure "
                                   f"{m['name']}")
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(used), "memory_peak_bytes": mem}
    out = {"correct": correct, "attempted": record["attempted"],
           "failed": record["failed"], "metrics": metrics, "device": device}
    if trace_red is not None:
        device["busy_s"] = trace_red["busy_s"]
        device["window_s"] = trace_red["window_s"]
        out["breakdown"] = {"device_ops": trace_red["device_ops"],
                            "idle_gaps": trace_red["idle_gaps"]}
    out["detail"] = {**kind.detail(), "windows": record["windows"],
                     "compiles_in_window": counter.n,
                     "window_overrun_s": t_ret - t_end}
    out["check"] = check
    return out


def _reports(metric: dict, cell: Cell, man: dict) -> bool:
    """Whether ``cell`` reports the per-layer ``metric``."""
    if "workloads" in metric:
        return cell.name in metric["workloads"]
    moves = next(m for m in man["end_to_end"] if m["name"] == metric["moves"])
    return "workloads" not in moves or cell.name in moves["workloads"]


def _rmtree(path):
    import shutil
    shutil.rmtree(path, ignore_errors=True)


def check_lines(out: dict) -> list:
    return [f"check {k} {v['value']!r} limit {v['limit']!r}"
            for k, v in out["check"].items()]


# ------------------------------------------------------------------ entry --
def main(argv=None, *, t_process: float) -> int:
    import argparse
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        if not (ROOT / "src" / "repro").is_dir():
            raise HarnessError("the program (src/repro) is not in this "
                               "checkout")
        sys.path.insert(0, str(ROOT / "src"))
        cell = load_cell(args.workload)
        import jax
        from repro.utils import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        out = run(cell, args.seed, args.seconds, bool(args.trace),
                  t_process=t_process)
    except HarnessError as e:
        print(f"run_cell: {e}", file=sys.stderr)
        return 3
    for line in check_lines(out):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out, default=float), flush=True)
    return 0
