"""The reference's own columns, found by name as files.

A configuration names its data set (``"dataset"``), and
``datasets/<dataset>/`` holds column providers: each ``*.py`` there says
which columns it gives and makes them at a scale factor,

    GIVES = {table: {column: {table: [column, ...]}}}   # what each is made from
    LABELS = {table: {column: (text of code 0, ...)}}   # optional
    def generate(scale_factor, wanted, have) -> {table: {column: ndarray}}

where ``wanted`` (``{table: [column, ...]}``) is what is asked of this
provider and ``have`` holds the columns of other providers that the wanted
ones are made from. A later PR adds a table, or a column of a table that
exists, by adding such a file; a column has one provider, and one that two
files give, or none, is a ``Refused``. Nothing here names a table or a column.

``load`` generates only what the templates read (and what that is made
from) and keeps a column a file under the cache directory: plain arrays, no
pickle, written by rename.
"""

from __future__ import annotations

import glob
import os

import numpy as np

from benchmark.files import Refused, load_module


class Tables(dict):
    """``{table: {column: ndarray}}``, with the letters of the coded columns
    under ``labels`` (``{table: {column: (text, ...)}}``)."""

    def __init__(self, columns, labels):
        super().__init__(columns)
        self.labels = labels


def pairs(reads: dict) -> list[tuple[str, str]]:
    return [(t, c) for t, cols in reads.items() for c in cols]


def by_table(columns) -> dict:
    """``{table: [column, ...]}`` of (table, column) pairs."""
    out: dict = {}
    for t, c in columns:
        out.setdefault(t, []).append(c)
    return out


class Dataset:
    """The providers of one data set, by the column each gives."""

    def __init__(self, data_root: str, name: str):
        self.name = name
        directory = os.path.join(data_root, "datasets", name)
        files = sorted(glob.glob(os.path.join(directory, "*.py")))
        if not files:
            raise Refused(f"no data set {name!r}: {directory} holds no column provider")
        self.provider: dict = {}
        self.labels: dict = {}
        for path in files:
            module = load_module(path, "column provider")
            if not hasattr(module, "GIVES") or not callable(getattr(module, "generate", None)):
                raise Refused(f"{path} is no column provider: it needs GIVES and generate()")
            for pair in pairs(module.GIVES):
                if pair in self.provider:
                    raise Refused(
                        f"two providers of {pair[0]}.{pair[1]}: {path} and "
                        f"{self.provider[pair].__file__}")
                self.provider[pair] = module
            for table, columns in getattr(module, "LABELS", {}).items():
                self.labels.setdefault(table, {}).update(columns)

    def made_from(self, pair) -> list:
        if pair not in self.provider:
            raise Refused(f"no provider of {pair[0]}.{pair[1]} in data set {self.name!r}")
        return pairs(self.provider[pair].GIVES[pair[0]][pair[1]])

    def check(self, reads: dict) -> None:
        """Refuse unless every column of ``reads``, and what it is made from,
        has its provider and none is made from itself."""

        def visit(pair, chain):
            if pair in chain:
                raise Refused(f"{pair[0]}.{pair[1]} is made from itself")
            for dep in self.made_from(pair):
                visit(dep, chain | {pair})

        for pair in pairs(reads):
            visit(pair, frozenset())

    def load(self, scale_factor: float, reads: dict, cache_dir: str | None = None) -> Tables:
        """The columns of ``reads``: from ``cache_dir`` where a run before
        this one left them, generated (and left there) where not."""
        self.check(reads)
        directory = None
        if cache_dir is not None:
            directory = os.path.join(cache_dir, f"{self.name}-sf{scale_factor:g}")
        got: dict = {}

        def need(wanted):
            missing = [p for p in wanted if p not in got]
            if directory is not None:
                for pair in missing:
                    if os.path.exists(_path(directory, pair)):
                        got[pair] = np.load(_path(directory, pair), allow_pickle=False)
            by_module: dict = {}
            for pair in missing:
                if pair not in got:
                    by_module.setdefault(self.provider[pair], []).append(pair)
            for module, asked in by_module.items():
                deps = sorted({d for pair in asked for d in self.made_from(pair)})
                need(deps)
                have = {t: {c: got[t, c] for c in cols} for t, cols in by_table(deps).items()}
                made = module.generate(scale_factor, by_table(asked), have)
                for pair in asked:
                    got[pair] = made[pair[0]][pair[1]]
                    if directory is not None:
                        _keep(directory, pair, got[pair])

        need(pairs(reads))
        return Tables(
            {t: {c: got[t, c] for c in cols} for t, cols in reads.items()}, self.labels)


def _path(directory: str, pair) -> str:
    return os.path.join(directory, "%s.%s.npy" % pair)


def _keep(directory: str, pair, array: np.ndarray) -> None:
    os.makedirs(directory, exist_ok=True)
    path = _path(directory, pair)
    tmp = f"{path}.{os.getpid()}.tmp.npy"
    np.save(tmp, array, allow_pickle=False)
    os.replace(tmp, path)


def union_of_reads(templates) -> dict:
    """``{table: sorted columns}`` over the templates' ``reads``."""
    out: dict = {}
    for template in templates:
        for table, columns in template.meta["reads"].items():
            out.setdefault(table, set()).update(columns)
    return {t: sorted(cols) for t, cols in sorted(out.items())}
