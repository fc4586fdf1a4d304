"""XLA device tracing, unified into the obs surface.

The reference's observability planes are the op log, the control audit
log, and post-hoc graphs (SURVEY.md §5); the accelerator-resident
checker adds XLA/TPU execution traces. ``xla_trace(dir)`` wraps any
checking code in a jax profiler capture viewable in TensorBoard /
Perfetto — `cli analyze --xla-trace DIR` and `bench --profile` both
ride it. The flight recorder is on for the capture, and each of its
spans holds a profiler annotation of its name, so the program's spans
sit in the capture's ``/host:`` plane on the device timeline's clock.
(This absorbed utils/profiling.py: one tracing stack, not two.)

jax is imported lazily so ``jepsen_tpu.obs`` itself stays stdlib-only.
"""

from __future__ import annotations

import contextlib

from jepsen_tpu.obs import trace as obs_trace


@contextlib.contextmanager
def xla_trace(log_dir: str):
    """Capture a device trace, with the span recorder on, for the
    enclosed block. Raises when the profiler cannot start (another
    capture running, no profiler on the backend): a caller who asked
    for a trace never silently gets none."""
    import jax

    jax.profiler.start_trace(log_dir)
    was_on = obs_trace.TRACER.enabled
    obs_trace.enable()
    try:
        yield
    finally:
        if not was_on:
            obs_trace.disable()
        jax.profiler.stop_trace()
