"""Bytes the program counted as sent to the device, per input event."""


def read(run):
    sent = run["counters"].get("device_transfer_bytes_h2d")
    if not sent or not run["events"]:
        return None
    return sent / run["events"]
