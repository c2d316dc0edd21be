from . import histogram, lookup, split, predict  # noqa: F401
