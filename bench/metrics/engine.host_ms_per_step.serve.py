"""The engine's host work per step while traced (ms)."""
import layer


def read(run):
    return layer.host_ms_per_step(run)
