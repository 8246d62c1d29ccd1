"""Share of the traced window with no operation on the device, %, averaged over the chips used."""


def read(rec):
    tr = rec.device_trace
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s)
