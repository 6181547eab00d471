"""device_idle_pct: share of the traced window in which no operation ran
on the chip, averaged over the cell's chips (100 * (1 - busy / window))."""


def read(red: dict, facts: dict):
    if not red.get("n_devices") or red.get("window_s", 0) <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
