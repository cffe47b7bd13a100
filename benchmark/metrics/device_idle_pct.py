"""1 minus the union of device-operation intervals over the traced
stretch, on the least busy chip."""


def read(run):
    trace = run.get("trace")
    if not trace or not trace["busy_by_device_s"]:
        return None
    least = min(trace["busy_by_device_s"].values())
    return 100.0 * (1.0 - least / trace["window_s"])
