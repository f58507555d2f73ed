"""Each architecture's files, found by the configuration's ``model_type``.

Everything the harness knows of one architecture sits in two files named
by its ``model_type``, so a new architecture joins as new files:

- ``arch/<model_type>.py``, the program's side and the arithmetic of
  costs: ``program_config(c)`` (the program's ``ModelConfig``, the
  program imported inside it), ``dims(c)`` (the sizes ``costs.py``
  needs), ``attention_flops(c, context)`` (one token's attention FLOPs
  in one layer), ``cache_leaves`` (the pool leaves of one layer's cache,
  in the order the reference returns its cache rows) and ``TINY`` (every
  key of the architecture at a size the CPU tests run in seconds);
- ``ref/<model_type>.py``, the reference's block: ``KEYS`` (the
  configuration keys its forward pass reads) and ``forward(params,
  tokens, c, ctl)``, which returns the final hidden states, each layer's
  cache rows as a tuple of arrays and, per position, the router margin.
  It imports nothing of the program.

This module imports nothing of the program either.
"""
from __future__ import annotations

import functools
import importlib.util
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
KINDS = ("arch", "ref")


class UnknownArchitecture(ValueError):
    pass


def known(kind: str = "arch") -> list:
    """The architectures that have a file under ``kind``/."""
    return sorted(p.stem for p in (HERE / kind).glob("*.py"))


def require(model_type: str) -> None:
    """Raise ``UnknownArchitecture``, naming the files found, unless
    ``model_type`` has both of its files."""
    if all((HERE / k / f"{model_type}.py").is_file() for k in KINDS):
        return
    found = "; ".join(f"{k}/ holds " + (", ".join(f"{t}.py" for t in known(k))
                                        or "nothing") for k in KINDS)
    raise UnknownArchitecture(
        f"no architecture {model_type!r} (it needs arch/{model_type}.py "
        f"and ref/{model_type}.py): {found}")


def load_file(path, name: str):
    """The Python file ``path``, loaded as the module ``name``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@functools.cache
def _load(kind: str, model_type: str):
    require(model_type)
    return load_file(HERE / kind / f"{model_type}.py",
                     f"bench_{kind}_{model_type}")


def program(model_type: str):
    """``arch/<model_type>.py``, loaded once."""
    return _load("arch", model_type)


def reference(model_type: str):
    """``ref/<model_type>.py``, loaded once."""
    return _load("ref", model_type)
