"""Share of the traced window in which no operation ran on the device, in
percent: 1 - union of the busy intervals / window, mean over the devices."""


def read(record):
    trace = record.get("device_trace") or {}
    if not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
