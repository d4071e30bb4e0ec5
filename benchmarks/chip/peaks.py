"""Published peaks of a chip, by JAX's ``device_kind``."""
from __future__ import annotations

import json
import os
from typing import Dict

PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "peaks.json")


def peaks(device_kind: str) -> Dict:
    """The peak table's entry; a device not in the table is an error."""
    with open(PATH) as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in {PATH}; known: {sorted(table)}")
    return table[device_kind]
