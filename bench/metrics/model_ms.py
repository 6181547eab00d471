"""model_ms: device time per step of the model's forward and backward
over the microbatch scan (``repro.obs.scopes.MODEL``, around
``train_step._accumulated_grads``), with every scope named under it,
averaged over chips: ``scope_incl_s["model"]`` of
``bench/scope_reduce.py``. None where the trace holds no such scope."""


def read(red: dict, facts: dict):
    secs = red.get("scope_incl_s", {}).get("model")
    if secs is None or facts["steps"] <= 0:
        return None
    return 1e3 * secs / facts["steps"]
