"""What every traffic generator shares: the request it makes, the
seed's streams, and the reading of a mix.

A mix (``bench/traffic/<name>.json``) is data only.  Its ``generator``
names the module ``bench/traffic/<generator>.py`` that turns it into
requests (``bench.harness.generator``); its ``loop`` tells the driver
how to offer them: ``open`` submits each when it is due, ``closed``
keeps ``clients`` requests in flight.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import numpy as np

__all__ = ["RequestSpec", "load_mix", "rng_for"]


@dataclasses.dataclass(frozen=True)
class RequestSpec:
    index: int
    prompt: np.ndarray       # (prompt_len,) int32
    max_new: int
    offset_s: float | None   # open loop: due time after the window opens


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent stream of the run's seed (any whole number)."""
    return np.random.default_rng([int(seed) % (1 << 64), stream])


def load_mix(path: Path) -> dict:
    mix = json.loads(Path(path).read_text())
    if mix.get("loop") not in ("open", "closed"):
        raise ValueError(f"{path}: loop must be 'open' or 'closed'")
    if mix["loop"] == "open" and mix["arrivals"]["rate"] <= 0:
        raise ValueError(f"{path}: arrival rate must be positive")
    if mix["loop"] == "closed" and mix["clients"] < 1:
        raise ValueError(f"{path}: a closed loop needs clients")
    return mix
