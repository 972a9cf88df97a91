"""``BENCHMARK.json`` and the data files it names.

A *root* is a directory that holds a ``BENCHMARK.json``; a *data
directory* holds ``traffic/``.  The real ones are the checkout and
``benchmark/``; the tests give a fixture of their own with tiny sizes.
"""

from __future__ import annotations

import dataclasses
import json
import re
from pathlib import Path
from typing import Any, Dict, List

PACKAGE_DIR = Path(__file__).resolve().parent
CHECKOUT = PACKAGE_DIR.parent
NAME = re.compile(r"[A-Za-z0-9][A-Za-z0-9_.-]{0,63}\Z")


class ManifestError(Exception):
    """The manifest or a file it names does not say what the harness
    needs."""


def load_json(path: Path) -> Dict[str, Any]:
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError) as e:
        raise ManifestError(f"{path}: {e}") from e
    if not isinstance(data, dict):
        raise ManifestError(f"{path}: not a JSON object")
    return data


def _named(entries: List[dict], name: str, what: str) -> dict:
    for entry in entries:
        if entry.get("name") == name:
            return entry
    known = ", ".join(str(e.get("name")) for e in entries)
    raise ManifestError(f"no {what} named {name!r} (there are: {known})")


@dataclasses.dataclass(frozen=True)
class Metric:
    """One entry of ``end_to_end`` or ``per_layer``."""

    name: str
    unit: str
    source: str
    end_to_end: bool

    @property
    def timed(self) -> bool:
        """Anything but a count the program makes is a time, a rate or a
        share of the device, and exists only on the chip."""
        return self.source != "program_counter"


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with the files it names, loaded."""

    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _metrics(manifest: dict, key: str, cell: str) -> List[Metric]:
    out = []
    for m in manifest.get(key, []):
        only = m.get("workloads")
        if only is not None and cell not in only:
            continue
        out.append(Metric(m["name"], m["unit"], m["source"],
                          end_to_end=key == "end_to_end"))
    return out


def load_cell(name: str, root: Path = CHECKOUT,
              data_dir: Path = PACKAGE_DIR) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``, with its
    configuration (``file`` in the manifest, relative to ``root``) and its
    traffic mix (``data_dir/traffic/<traffic>.json``)."""
    manifest = load_json(Path(root) / "BENCHMARK.json")
    entry = _named(manifest.get("workloads", []), name, "workload")
    cfg_entry = _named(manifest.get("configs", []), entry["config"],
                       "configuration")
    for label in (entry["name"], entry["config"], entry["traffic"]):
        if not NAME.match(label):
            raise ManifestError(f"{label!r} is not a plain name")
    config = load_json(Path(root) / cfg_entry["file"])
    config.setdefault("name", cfg_entry["name"])
    traffic = load_json(
        Path(data_dir) / "traffic" / f"{entry['traffic']}.json")
    traffic.setdefault("name", entry["traffic"])
    chips = int(entry["chips"])
    if chips not in (1, 4):
        raise ManifestError(f"{name}: chips is {chips}, not 1 or 4")
    return Cell(
        name=name, chips=chips, config=config, traffic=traffic,
        end_to_end=_metrics(manifest, "end_to_end", name),
        per_layer=_metrics(manifest, "per_layer", name),
    )
