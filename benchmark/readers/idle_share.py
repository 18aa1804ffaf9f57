"""1 - busy_s / window_s of the traced window, in %."""


def read(data, args):
    t = data["trace"]
    return None if t is None else \
        100.0 * (1.0 - t["busy_s"] / t["window_s"])
