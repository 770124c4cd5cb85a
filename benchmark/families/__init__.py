"""Model families: one module per architecture the benchmark can hold the
program to.  A configuration file names its family; a later PR that adds an
architecture adds a module here and edits none."""

import importlib


def load(name: str):
    return importlib.import_module(f"{__name__}.{name}")
