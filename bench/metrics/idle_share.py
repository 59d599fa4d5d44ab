"""idle_share: per cent of the traced window in which no operation ran
on the device (1 - union of device-op intervals / window)."""


def read(red: dict):
    if not red["window_ns"] or not red["n_devices"]:
        return None
    return 100.0 * (1.0 - red["busy_ns"] / red["window_ns"])
