"""Published peaks of the devices the benchmark runs on, keyed by JAX's
``device_kind``.  A device that is not in ``peaks.json`` is an error, not a
default: a roofline share over a guessed peak is no number."""

from __future__ import annotations

import os

from .spec import SpecError, read_json

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks_for(device_kind: str) -> dict:
    table = read_json(_PATH)["devices"]
    if device_kind not in table:
        raise SpecError(f"no published peaks for device kind {device_kind!r} "
                        f"in harness/peaks.json (known: {sorted(table)})")
    return dict(table[device_kind])
