"""Share of the traced window with no device op running (%)."""
import layer


def read(run):
    return layer.idle_share(run)
