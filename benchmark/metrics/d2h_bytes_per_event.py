"""Bytes the program counted as read back from the device, per input
event."""


def read(run):
    back = run["counters"].get("device_transfer_bytes_d2h")
    if not back or not run["events"]:
        return None
    return back / run["events"]
