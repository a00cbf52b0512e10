"""The paper's primary contribution, adapted to JAX/TPU (DESIGN.md §2):
P-Shell instrumentation shell, step-locked co-emulation vs golden models,
toggle coverage, stall-stack profiling, event-driven timing models, and
Scale-Down subsystem decomposition."""
from repro.core.pshell import (  # noqa: F401
    FifoSpec, ShellConfig, PShell, shell_init, csr_read, csr_write,
    csr_accum, fifo_push, fifo_push_many, drain, group_reset,
    stack_batches)
from repro.core.schedule import (  # noqa: F401
    WindowScheduler, WindowPlan, DrainBarrier, Client, ClientDriver,
    plan_windows, iter_windows)
from repro.core.commit import default_shell_config, make_ingest  # noqa: F401
from repro.core.coemu import CoEmulator  # noqa: F401
from repro.core.coverage import CoverageMap  # noqa: F401
from repro.core.profiler import Profiler, StallStack  # noqa: F401
from repro.core.timing import Timeline, Event, InterfaceTimer  # noqa: F401
from repro.core.watchdog import Watchdog  # noqa: F401
from repro.core.scope import (  # noqa: F401
    ScopeSpec, ScopePlane, instrument, digest_tree)
