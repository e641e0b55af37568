"""SGD, with momentum where the traffic file gives one.
Traffic: {"name": "sgd", "learning_rate": ..., "momentum": ...}."""


def make(spec: dict):
    import optax

    return optax.sgd(spec["learning_rate"], momentum=spec.get("momentum"))
