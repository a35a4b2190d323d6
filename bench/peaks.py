"""Published peaks of a chip, looked up by the ``device_kind`` JAX
reports. A device that is not in ``peaks.json`` is an error, not a
default: a roofline against the wrong peak would be a wrong number."""
from __future__ import annotations

import json
import pathlib
from typing import Dict

TABLE = pathlib.Path(__file__).resolve().parent / "peaks.json"


def lookup(device_kind: str) -> Dict[str, float]:
    devices = json.loads(TABLE.read_text())["devices"]
    if device_kind not in devices:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in {TABLE.name}; add its row "
                       "with its source")
    return devices[device_kind]
