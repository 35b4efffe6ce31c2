"""Host milliseconds per query tick from packed buffers to a dispatched
program: the fused buffer and the host-to-device copy (``storm.gw.flatten``
and ``storm.gw.launch`` spans)."""

from storm_bench.program_spans import dispatch_ms as read  # noqa: F401
