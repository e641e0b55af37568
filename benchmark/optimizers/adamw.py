"""AdamW, optax's defaults but for the learning rate.
Traffic: {"name": "adamw", "learning_rate": ...}."""


def make(spec: dict):
    import optax

    return optax.adamw(spec["learning_rate"])
