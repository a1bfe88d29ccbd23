"""The one general traffic generator.  A mix is a data file under
``benchmarks/traffic/``; this module finds it by name and turns
``--seed`` into the seeds of a run's streams (weights, inputs).  Adding
a mix adds a file, never code here.

Every seed offers the same work: a mix fixes every size (batch, image
or sequence length), and the seed changes only the values drawn.  So a
difference between two seeds is the system's, not the draw's.
"""

from __future__ import annotations

import json
import os
import random
from typing import Dict

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str, directory: str = None) -> Dict:
    path = os.path.join(directory or os.path.join(HERE, "traffic"),
                        name + ".json")
    with open(path) as f:
        mix = json.load(f)
    if mix.get("name") != name:
        raise ValueError(f"{path}: \"name\" is {mix.get('name')!r}, the "
                         f"file is found as {name!r}")
    return mix


def fold_seed(seed: int, stream: int = 0) -> int:
    """A 31-bit seed for generators that take no more (``--seed`` may be
    a little over 2**31), different per ``stream``."""
    return random.Random(f"{int(seed)}/{int(stream)}").getrandbits(31)
