"""Share of the traced window the devices spent in collective operations
(opcode all-reduce, all-gather, reduce-scatter, collective-permute,
all-to-all, and their ``-start``/``-done`` halves), in percent, mean over the
devices. From the ``XLA Ops`` line: a synchronous collective counts in full,
an asynchronous one only while the core sits in its start and done ops, so
this is nearer the exposed time than the time in flight."""


def read(record):
    trace = record.get("device_trace") or {}
    if not trace.get("window_s") or trace.get("devices", 1) < 2:
        return None
    return 100.0 * trace["category_s"].get("collective", 0.0) / trace["window_s"]
