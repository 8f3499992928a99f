"""Share of the device's busy time spent in Mosaic (Pallas) kernels, in
percent: device time of the ``custom-call`` ops over the busy time."""


def read(record):
    trace = record.get("device_trace") or {}
    if not trace.get("busy_s"):
        return None
    return 100.0 * trace["category_s"].get("mosaic", 0.0) / trace["busy_s"]
