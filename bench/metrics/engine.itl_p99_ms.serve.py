"""Every gap between consecutive output tokens in the window, p99 (ms)."""
import layer


def read(run):
    return layer.itl_p99_ms(run)
