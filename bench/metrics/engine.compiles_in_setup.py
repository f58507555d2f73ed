"""Programs jax traced and lowered in set-up, before the lead-in (count):
the engine's step, once for each token count it can take."""


def read(run):
    return run["setup_compiles"]
