"""FarmTelemetry: report schema (tail percentiles, per-slot stall-stack
attribution, device-side scope channel) and bounded-log behavior under
concurrent slot-thread writers."""
from __future__ import annotations

import threading

import pytest

from repro.farm.telemetry import FarmTelemetry, _BoundedLog, _stats


# ---------------------------------------------------------- percentiles --
def test_stats_reports_tail_percentiles():
    """Every latency channel carries n/mean/p50/p95/p99/max — nearest
    rank, so on 1..100 the percentiles are exact."""
    st = _stats([float(i) for i in range(1, 101)])
    assert st["n"] == 100
    assert st["mean"] == pytest.approx(50.5)
    assert st["p50"] == 51.0            # upper median (len // 2)
    assert st["p95"] == 95.0
    assert st["p99"] == 99.0
    assert st["max"] == 100.0
    assert _stats([]) == {"n": 0}
    one = _stats([7.0])
    assert one["p50"] == one["p95"] == one["p99"] == one["max"] == 7.0


def test_report_channel_schema_includes_percentiles():
    fake = {"t": 0.0}
    tm = FarmTelemetry(clock=lambda: fake["t"])
    for i in range(20):
        tm.dispatch("slot0", i, cost_s=0.001 * (i + 1))
        fake["t"] += 0.010
        tm.drain("slot0", i, wall_s=0.002)
    dev = tm.report()["devices"]["slot0"]
    assert dev["windows"] == 20
    for ch in ("window_ms", "dispatch_ms", "drain_ms"):
        for k in ("n", "mean", "p50", "p95", "p99", "max"):
            assert k in dev[ch], (ch, k)
    assert dev["window_ms"]["p50"] == pytest.approx(10.0)
    assert dev["dispatch_ms"]["p99"] == pytest.approx(20.0)


def test_admission_counter_accumulates_ticks():
    """The control table's admission counter sums every tick, and reads
    zero on a farm that never admitted asynchronously."""
    tm = FarmTelemetry()
    assert tm.report()["control"]["admission"] == {
        "ticks": 0, "examined": 0, "assigned": 0}
    tm.admission(examined=3, assigned=1)
    tm.admission(examined=0, assigned=0)
    tm.admission(examined=2, assigned=4)    # a fused lane run seats four
    assert tm.report()["control"]["admission"] == {
        "ticks": 3, "examined": 5, "assigned": 5}


# ------------------------------------------------------------ stall stack --
def test_dominant_stall_attribution_per_slot():
    """The slot's host-overhead channel sums fold into a StallStack whose
    dominant term is surfaced — the solo Profiler attribution rebuilt
    farm-side."""
    tm = FarmTelemetry()
    tm.queue_wait("slot0", 0.001)
    tm.dispatch("slot0", 0, cost_s=0.050)
    tm.drain("slot0", 0, wall_s=0.002)
    tm.idle("slot0", 0.003)
    dev = tm.report()["devices"]["slot0"]
    assert dev["dominant_stall"] == "dispatch"
    assert set(dev["stall_ms"]) == {"queue", "dispatch", "drain", "idle"}
    assert dev["stall_ms"]["dispatch"] == pytest.approx(50.0)
    assert "stall: dispatch" in tm.summary()


def test_dominant_stall_absent_without_samples():
    tm = FarmTelemetry()
    tm.dispatch("slot0", 0, cost_s=0.0)
    tm.drain("slot0", 0)
    assert tm.report()["devices"]["slot0"]["dominant_stall"] is None


# ------------------------------------------------------------ bounded log --
def test_bounded_log_reports_dropped_count():
    log = _BoundedLog(maxlen=4)
    for i in range(10):
        log.append(i)
    assert len(log) == 4
    assert list(log) == [6, 7, 8, 9]    # newest retained
    assert log.dropped == 6


def test_bounded_log_dropped_under_concurrent_slot_writers():
    """Many slot threads appending through the telemetry lock: no event
    is lost silently — retained + dropped accounts for every append, and
    the report surfaces the drop count per log."""
    tm = FarmTelemetry(max_events=64)
    threads, per_thread, n_threads = [], 200, 8

    def slot_writer(k):
        for i in range(per_thread):
            tm.scope(f"slot{k}", f"job{k}",
                     {"windows": i + 1, "steps": i + 1, "tokens": 1.0,
                      "d_windows": 1, "d_steps": 1, "d_tokens": 1.0,
                      "lanes": 1, "quiet": False})
            tm.eviction(f"slot{k}", f"job{k}", "straggler")

    for k in range(n_threads):
        t = threading.Thread(target=slot_writer, args=(k,),
                             name=f"slot{k}")
        threads.append(t)
        t.start()
    for t in threads:
        t.join()

    total = per_thread * n_threads
    assert len(tm.scope_samples) == 64
    assert tm.scope_samples.dropped == total - 64
    assert len(tm.evictions) == 64
    assert tm.evictions.dropped == total - 64
    rep = tm.report()
    assert rep["events_dropped"]["scope_samples"] == total - 64
    assert rep["events_dropped"]["evictions"] == total - 64
    # the per-job cumulative table is NOT bounded: it keeps the latest
    # row for every job regardless of log truncation
    assert len(rep["scope"]["jobs"]) == n_threads
    for k in range(n_threads):
        assert rep["scope"]["jobs"][f"job{k}"]["windows"] == per_thread


# ----------------------------------------------------------- scope channel --
def test_scope_report_schema_and_quiet_counts():
    tm = FarmTelemetry()
    tm.scope("slot0", "train",
             {"lanes": 1, "windows": 8, "steps": 16, "tokens": 64.0,
              "gates": [0, 0, 1, 1], "digest": 123, "d_windows": 8,
              "d_steps": 16, "d_tokens": 64.0, "quiet": False})
    tm.scope("slot0", "train",
             {"lanes": 1, "windows": 8, "steps": 16, "tokens": 64.0,
              "gates": [0, 0, 1, 1], "digest": 123, "d_windows": 0,
              "d_steps": 0, "d_tokens": 0.0, "quiet": True})
    tm.scope("slot1", "lanes",
             {"lanes": 2, "windows": 4, "steps": 8,
              "tokens": [16.0, 24.0], "gates": [[0, 0, 1, 1]] * 2,
              "digest": [5, 6], "d_windows": 4, "d_steps": 8,
              "d_tokens": 40.0, "quiet": False})
    sc = tm.scope_report()
    assert sc["samples"] == 3 and sc["samples_dropped"] == 0
    assert sc["quiet_samples"] == 1
    train = sc["jobs"]["train"]
    assert train["slot"] == "slot0"
    assert train["tokens_per_window"] == pytest.approx(8.0)
    assert train["quiet_samples"] == 1
    lanes = sc["jobs"]["lanes"]
    assert lanes["tokens_per_window"] == pytest.approx([4.0, 6.0])
    # the same table rides the full report and the summary line
    assert tm.report()["scope"]["jobs"].keys() == {"train", "lanes"}
    assert "scope: 3 samples over 2 jobs" in tm.summary()
    assert "1 quiet intervals excluded" in tm.summary()
