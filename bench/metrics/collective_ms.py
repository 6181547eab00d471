"""collective_ms: device time per step of the collective operations
(all-to-all, all-gather, all-reduce, reduce-scatter, permutes), the union
of their intervals on each chip, averaged over chips. Nothing to read on
one chip, where no exchange runs."""


def read(red: dict, facts: dict):
    if facts["chips"] < 2 or not red.get("n_devices") or facts["steps"] <= 0:
        return None
    return 1e3 * red["collective_s"] / facts["steps"]
