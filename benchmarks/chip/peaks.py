"""The chip's peaks, from ``peaks.json`` (which names its source), keyed
by JAX's ``device_kind``."""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

HERE = Path(__file__).resolve().parent


def peaks(device_kind: str) -> Dict[str, float]:
    """The chip's peaks; a device that the table lacks is an error."""
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]
