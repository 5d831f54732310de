"""Data generators, one module per kind, named by a configuration's
``data.generator``."""
