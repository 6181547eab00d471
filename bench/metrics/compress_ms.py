"""compress_ms: device time per step of compression, the error-feedback
add, bucketed top-k and the residual fold (``repro.obs.scopes.COMPRESS``,
in ``comm/executor.py`` around ``core/topk.compress2d``), with every scope
named under it, averaged over chips: ``scope_incl_s["sync/compress"]`` of
``bench/scope_reduce.py``. None where the trace holds no such scope."""


def read(red: dict, facts: dict):
    secs = red.get("scope_incl_s", {}).get("sync/compress")
    if secs is None or facts["steps"] <= 0:
        return None
    return 1e3 * secs / facts["steps"]
