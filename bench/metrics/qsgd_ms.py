"""qsgd_ms: device time per step of QSGD, the rounding bits, quantize and
dequantize (``repro.obs.scopes.QSGD``: ``comm/executor``'s ``_qsgd_rand``,
``core/allreduce``'s pack and unpack), with every scope named under it,
averaged over chips: ``scope_incl_s["sync/qsgd"]`` of
``bench/scope_reduce.py``. None where the trace holds no such scope."""


def read(red: dict, facts: dict):
    secs = red.get("scope_incl_s", {}).get("sync/qsgd")
    if secs is None or facts["steps"] <= 0:
        return None
    return 1e3 * secs / facts["steps"]
