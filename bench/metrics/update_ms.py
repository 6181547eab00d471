"""update_ms: device time per step of the update, clipping, AdamW and
ZeRO-1's parameter all-gather (``repro.obs.scopes.UPDATE``: ``optim/``,
``train_step._zero1_update``), with every scope named under it, averaged
over chips: ``scope_incl_s["update"]`` of ``bench/scope_reduce.py``. None
where the trace holds no such scope."""


def read(red: dict, facts: dict):
    secs = red.get("scope_incl_s", {}).get("update")
    if secs is None or facts["steps"] <= 0:
        return None
    return 1e3 * secs / facts["steps"]
