"""Modules found by a name that a data file gives: the generator kinds
(``kinds/<kind>.py``) and the plain references (``references/<name>.py``).
A later PR adds a file; nothing here or in ``run.py`` names one."""

from __future__ import annotations

import importlib
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_]{0,63}$")


class Unknown(ValueError):
    """The data names a module that no file provides."""


def load(package: str, name: str, needs: str):
    """``<package>/<name>.py`` as a module that has the attribute ``needs``."""
    have = sorted(f[:-3] for f in os.listdir(os.path.join(HERE, package))
                  if f.endswith(".py") and not f.startswith("_"))
    if not isinstance(name, str) or not NAME.match(name) or name not in have:
        raise Unknown(f"no {package}/{name}.py: this benchmark has {have}")
    module = importlib.import_module(f"{package}.{name}")
    if not callable(getattr(module, needs, None)):
        raise Unknown(f"{package}/{name}.py gives no {needs}()")
    return module
