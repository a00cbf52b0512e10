"""The reduction from a profiler trace to device numbers (chip/trace.py):
on a synthetic trace in the TPU layout whose answer is known, and on a
small trace recorded on a TPU v5e (``data/record_trace.py``) and one
recorded on the CPU."""
import pytest
from jax.profiler import ProfileData

import smoke
from chip import stats, trace

US = 1_000_000          # picoseconds per microsecond

SYNTHETIC = f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: {1 * US} duration_ps: {4 * US} }}
    events {{ metadata_id: 2 offset_ps: {3 * US} duration_ps: {4 * US} }}
  }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0
    events {{ metadata_id: 3 offset_ps: {1 * US} duration_ps: {6 * US} }}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "fusion.1" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "fusion.2" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "jit_group_step(7)" }} }}
}}
planes {{
  id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: {20 * US} }}
    events {{ metadata_id: 2 offset_ps: {8 * US} duration_ps: {7 * US} }}
    events {{ metadata_id: 3 offset_ps: {8 * US} duration_ps: {1 * US} }}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "bench.window" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "bench.verify" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "other.span" }} }}
}}
"""


def test_overlapping_intervals_count_once():
    assert stats.union_length([(1, 5), (3, 7)]) == 6
    assert stats.union_length([(1, 5), (2, 3), (9, 10)]) == 5
    assert stats.merged([(3, 7), (1, 5), (8, 9)]) == [(1, 7), (8, 9)]


def test_synthetic_trace_reduction():
    red = trace.reduce(trace.events(ProfileData.from_text_proto(SYNTHETIC)))
    assert red["window_s"] == pytest.approx(20e-6)
    # fusion.1 [1, 5] and fusion.2 [3, 7] overlap: busy 6 us, not 8
    assert red["busy_s"] == pytest.approx(6e-6)
    assert red["ops"]["fusion.1"] == pytest.approx(4e-6)
    assert red["modules"] == {"jit_group_step": pytest.approx(6e-6)}
    assert red["module_counts"] == {"jit_group_step": 1.0}
    # gaps [0, 1] and [7, 20]; the long one is named by bench.verify
    assert red["idle_gaps"][0] == ["bench.verify", pytest.approx(13e-6)]
    assert red["idle_gaps"][1] == ["no_bench_span", pytest.approx(1e-6)]


def test_recorded_cpu_trace_has_spans_and_no_device():
    """A trace recorded on the CPU (``data/cpu_small.xplane.pb``: three
    jitted calls under ``bench.dispatch`` inside ``bench.window``, with
    ``bench.verify`` sleeps): the benchmark's host spans are found, and
    the reduction refuses it, since no operation ran on a device."""
    ev = trace.load(str(smoke.HERE / "data" / "cpu_small.xplane.pb"))
    names = [n for n, _, _ in ev["spans"]]
    assert names.count("bench.window") == 1
    assert names.count("bench.dispatch") == 3
    assert names.count("bench.verify") == 3
    assert all(s <= e for _, s, e in ev["spans"])
    assert ev["devices"] == {}
    with pytest.raises(ValueError, match="no device plane"):
        trace.reduce(ev)


def test_recorded_tpu_trace_reduction():
    """A trace recorded on a TPU v5e (``data/tpu_small.xplane.pb``, by
    ``data/record_trace.py``: a matmul and a reduction, three times each,
    each under ``bench.dispatch`` inside ``bench.window``, with
    ``bench.verify`` sleeps between them): the device plane is found with
    its ops and programs, busy time is the union of the op intervals and
    lies inside the window, and the long idle gaps are named by the host
    span that covers them."""
    ev = trace.load(str(smoke.HERE / "data" / "tpu_small.xplane.pb"))
    assert list(ev["devices"]) == ["/device:TPU:0"]
    dev = ev["devices"]["/device:TPU:0"]
    assert len(dev["modules"]) == 6
    assert all(trace.module_name(n) == "jit__lambda" for n, _, _ in
               dev["modules"])
    names = [n for n, _, _ in ev["spans"]]
    assert names.count("bench.window") == 1
    assert names.count("bench.dispatch") == 6
    assert names.count("bench.verify") == 3
    red = trace.reduce(ev)
    (lo, hi), = [(s, e) for n, s, e in ev["spans"] if n == "bench.window"]
    ops = [(s, e) for _, s, e in dev["ops"] if e > lo and s < hi]
    assert red["window_s"] == pytest.approx(hi - lo)
    assert 0 < red["busy_s"] < red["window_s"]
    assert red["busy_s"] == pytest.approx(stats.union_length(
        (max(s, lo), min(e, hi)) for s, e in ops))
    assert "convolution_tanh_fusion" in red["device_ops"][0][0]
    # the programs' spans hold the ops, give or take their launch
    assert red["modules"]["jit__lambda"] == pytest.approx(red["busy_s"],
                                                          rel=0.01)
    assert red["idle_gaps"][0][0] == "bench.verify"
    assert red["idle_gaps"][0][1] > 1e-3
