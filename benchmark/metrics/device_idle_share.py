"""Layer: device.  1 - union of device-operation intervals over the traced
slice, in percent (on several chips, of the busiest device)."""


def read(facts):
    trace = facts["trace"]
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s_fullest"] / trace["window_s"])
