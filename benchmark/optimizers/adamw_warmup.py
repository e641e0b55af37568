"""AdamW, optax's defaults, under a linear warm-up of the learning rate: 0
at step 0, `learning_rate` at step `warmup_steps` and constant after it.
Traffic: {"name": "adamw_warmup", "learning_rate": ..., "warmup_steps": ...}."""


def schedule(spec: dict):
    """step -> rate. A spec without `warmup_steps` is refused (KeyError):
    the constant rate is `adamw`'s."""
    import optax

    return optax.linear_schedule(0.0, spec["learning_rate"], spec["warmup_steps"])


def make(spec: dict):
    import optax

    return optax.adamw(schedule(spec))
