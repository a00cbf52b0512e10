"""Host time of the farm's control plane per drained window: the mean of
the telemetry's dispatch_ms + drain_wall_ms (slot threads, host clock)."""


def read(rec):
    return rec.get("host_ms_per_window")
