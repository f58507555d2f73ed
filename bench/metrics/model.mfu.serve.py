"""Model FLOPs of the traced steps over window x peak (%)."""
import layer


def read(run):
    return layer.mfu(run)
