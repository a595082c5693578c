"""The chip's published peaks, keyed by `device_kind`. A kind that is not
in peaks.json is an error, never a default: an assumed peak makes every
share of it wrong without a trace."""
from __future__ import annotations

import json
import os


def peaks(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)["kinds"]
    if device_kind not in table:
        raise KeyError(f"no peaks known for device kind {device_kind!r}; "
                       f"add it to benchmarks/peaks.json with its source")
    return table[device_kind]
