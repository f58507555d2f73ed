"""Programs jax traced and lowered inside the window (count)."""


def read(run):
    return run["compiles"]
