"""Files found by name: what every by-name lookup of the benchmark shares.

A per-layer metric's reader (``metrics/<name>.py``), a query's reference
function (``references/<name>.py``) and a data set's column providers
(``datasets/<dataset>/*.py``) are Python files that a later PR adds beside
the ones that are there. They are loaded by path, never imported by a name
this package would have to list.
"""

from __future__ import annotations

import importlib.util
import os


class Refused(Exception):
    """The run cannot be made here: no result line, exit code 2."""


def load_module(path: str, what: str):
    """The module in the file ``path``; ``what`` says what it is to be, for
    the refusal when it is not there."""
    if not os.path.isfile(path):
        raise Refused(f"no {what}: {path} is not there")
    stem = os.path.splitext(os.path.basename(path))[0]
    kind = os.path.basename(os.path.dirname(path))
    spec = importlib.util.spec_from_file_location(f"benchmark_{kind}_{stem}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
