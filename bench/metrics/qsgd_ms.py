"""qsgd_ms: device self time per step of QSGD, the rounding bits,
quantize and dequantize (``repro.obs.scopes.QSGD``: ``comm/executor``'s
``_qsgd_rand``, ``core/allreduce``'s pack and unpack), averaged over
chips: ``scope_s["sync/qsgd"]`` of ``bench/scope_reduce.py``. None where
the trace holds no such scope."""


def read(red: dict, facts: dict):
    secs = red.get("scope_s", {}).get("sync/qsgd")
    if secs is None or facts["steps"] <= 0:
        return None
    return 1e3 * secs / facts["steps"]
