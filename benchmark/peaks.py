"""The table of device peaks every utilization is divided by."""

from __future__ import annotations

from typing import Any, Dict

from .manifest import PACKAGE_DIR, load_json


class UnknownDevice(Exception):
    """A device kind with no row in ``peaks.json``: an error, not a
    default."""


def for_kind(device_kind: str) -> Dict[str, Any]:
    """The row of ``peaks.json`` for jax's ``device_kind``."""
    kind = (device_kind or "").lower()
    for row in load_json(PACKAGE_DIR / "peaks.json")["devices"]:
        if row["kind"] in kind:
            return row
    raise UnknownDevice(
        f"no peaks for device_kind {device_kind!r}: add its datasheet row "
        "to benchmark/peaks.json")
