"""Data generators, one module per configuration family.

A configuration's ``generator`` names a module here; its
``make(cfg, key, seed, devices)`` (the devices the cell uses) returns a :class:`Dataset`.  These are
copies kept with the benchmark, so the yardstick does not move when the
program's own generators do.
"""
from __future__ import annotations

import dataclasses
from collections.abc import Callable
from typing import Any


@dataclasses.dataclass
class Dataset:
    """What a run factorizes.

    ``operand`` is passed to the program as the matrix and ``kwargs``
    beside it.  ``dense()`` gives X as one device array, in the
    layout the reference reads.
    """

    operand: Any
    kwargs: dict
    dense: Callable[[], Any]
