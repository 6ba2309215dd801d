"""Names of the serving program's spans in the profiler's trace.

The engine and the front end open each span as a
``jax.profiler.TraceAnnotation`` (the engine round as a
``StepTraceAnnotation``), so a trace taken with ``jax.profiler.start_trace``
or ``start_server`` holds them on the same clock as the device's
programs.  The work counts a metric reads ride on a span as its stats:
host ints, never a device value, so a span never waits for the device.
With the profiler off a span records nothing and costs about a
microsecond.  docs/SERVING.md §Tracing lists the spans and their stats.
"""

STEP = "serve.step"
ADMIT = "serve.admit"
PREFILL_CHUNK = "serve.prefill_chunk"
FIRST_TOKEN = "serve.first_token"
DECODE_DISPATCH = "serve.decode_dispatch"
HOST_SYNC = "serve.host_sync"
RETIRE = "serve.retire"
ACCOUNTING = "serve.accounting"
FRONTEND_PUMP = "frontend.pump"
FRONTEND_SUBMIT = "frontend.submit"
