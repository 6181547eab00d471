"""exposed_collective_ms: the part of ``collective_ms`` in which no other
operation ran on that chip, per step, averaged over chips."""


def read(red: dict, facts: dict):
    if facts["chips"] < 2 or not red.get("n_devices") or facts["steps"] <= 0:
        return None
    return 1e3 * red["exposed_collective_s"] / facts["steps"]
