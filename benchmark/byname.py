"""How the harness finds what belongs to one table kind, request kind
or reader: the file ``<directory>/<name>.py`` beside this one."""

from __future__ import annotations

import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load(directory: str, name: str):
    path = os.path.join(HERE, directory, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(
            f"benchmark/{directory}/ has no {name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{directory}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
