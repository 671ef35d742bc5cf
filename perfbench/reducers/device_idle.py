"""Share of the traced window in which no operation ran on the device:
100 x (1 - union of the device operations' intervals / window), the mean
over the chips used."""


def reduce(params, src):
    s = src.summary
    if not s or not s["window_s"]:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
