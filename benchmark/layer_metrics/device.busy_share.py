"""Share of the traced window in which an operation ran on the device, in
percent (the complement of ``device.idle_share``, for the cells judged on
tokens per second)."""


def read(record):
    trace = record.get("device_trace") or {}
    if not trace.get("window_s"):
        return None
    return 100.0 * trace["busy_s"] / trace["window_s"]
