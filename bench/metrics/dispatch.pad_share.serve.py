"""Share of computed expert rows that were padding (%)."""
import layer


def read(run):
    return layer.pad_share(run)
