"""1 - (union of device-busy intervals / traced window), in percent."""


def read(record):
    tr = record.trace
    if tr is None or not tr["devices"] or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
