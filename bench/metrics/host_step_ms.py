"""host_step_ms: the host's serial work per step of the closed loop,
``train/run`` less ``train/wait`` (``host_span_s`` of
``bench/scope_reduce.py``): what ``Trainer.run`` does while no step of
its own runs on the chip, which the chip waits behind."""


def read(red: dict, facts: dict):
    spans = red.get("host_span_s", {})
    if "train/run" not in spans or facts["steps"] <= 0:
        return None
    return 1e3 * (spans["train/run"] - spans.get("train/wait", 0.0)) \
        / facts["steps"]
