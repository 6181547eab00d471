"""mfu: model FLOP utilisation of the traced window: the model FLOPs of
every token trained in it (``bench/flops.py``, recomputation not counted)
over the window's length, over chips x the chip's bf16 peak."""


def read(red: dict, facts: dict):
    window = red.get("window_s", 0)
    if not red.get("n_devices") or window <= 0 or facts["steps"] <= 0:
        return None
    flops = facts["flops_per_token"] * facts["tokens_per_step"] * facts["steps"]
    return 100.0 * flops / window / (facts["chips"] * facts["peak_flops_per_s"])
