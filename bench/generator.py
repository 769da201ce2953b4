"""The one traffic generator: a traffic file's parameters and a
configuration, made into a cell the harness can drive.

A traffic file (``bench/traffic/<mix>.json``) names its ``entry``, the
user-facing simulator entry point it drives, and the parameters of
that entry's grid.  The entry's module, ``bench/entries/<entry>.py``,
found by that name, defines ``Cell(config, traffic, seed, devices)``:
it builds the call's inputs from the configuration, the traffic and
``--seed``, runs the call (``call()``), and checks the window's answers
against the plain references in ``bench/reference`` (``check(outs,
control=False)``).  It also gives ``bursts_per_call``, accuracy lines
for standard error (``notes(out)``) and ``close()``.  A new mix of an
entry is a new data file; a new entry is a new module.

Every call of a cell does the same work on the same inputs: the seed
changes the data (an allocation, a layer), never the sizes, so every
seed compiles the same programs.
"""
from __future__ import annotations

import dataclasses
import hashlib
import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclasses.dataclass
class Check:
    """The numbers compared, each with its limit, and whether every
    one of them is within its limit."""
    numbers: dict                 # name -> (value, limit)
    failed_calls: int

    @property
    def correct(self) -> bool:
        return all(v <= lim for v, lim in self.numbers.values())


def digest(obj) -> str:
    return hashlib.sha256(json.dumps(obj, sort_keys=True).encode()
                          ).hexdigest()


def verdict(numbers: dict, digests: list[str]) -> Check:
    """The check of a window whose last call's answers gave the
    reference mismatches ``numbers``: every other call must have given
    the same answers.  A call fails if the checked answers were wrong or
    its own differ from them."""
    unlike = sum(d != digests[-1] for d in digests)
    wrong = any(v > lim for v, lim in numbers.values())
    return Check({**numbers, "calls_unlike_checked": (unlike, 0)},
                 failed_calls=unlike + (len(digests) - unlike) * wrong)


def build(config: dict, traffic: dict, seed: int, devices, root: str = ROOT):
    """The cell a traffic file describes, on ``devices``."""
    name = str(traffic.get("entry", ""))
    entries = os.path.join(root, "bench", "entries")
    path = os.path.join(entries, name + ".py")
    if not name.isidentifier() or not os.path.isfile(path):
        known = sorted(f[:-3] for f in os.listdir(entries)
                       if f.endswith(".py") and not f.startswith("_"))
        raise ValueError(f"unknown traffic entry {name!r}; known: {known}")
    spec = importlib.util.spec_from_file_location(f"bench_entry_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.Cell(config, traffic, seed, devices)
