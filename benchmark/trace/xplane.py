"""An ``.xplane.pb`` as plain Python: planes, their lines, and events with
a start and an end in nanoseconds on one clock."""

from __future__ import annotations

import dataclasses
import re
from pathlib import Path
from typing import Dict, List, Optional

DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)\Z")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Event:
    name: str
    start: float
    end: float


@dataclasses.dataclass
class Plane:
    name: str
    lines: Dict[str, List[Event]]


@dataclasses.dataclass
class Trace:
    planes: List[Plane]

    def devices(self) -> List[Plane]:
        """The chips' planes, in the order of their numbers."""
        found = [(int(DEVICE_PLANE.match(p.name).group(1)), p)
                 for p in self.planes if DEVICE_PLANE.match(p.name)]
        return [p for _, p in sorted(found, key=lambda x: x[0])]

    def host(self) -> Optional[Plane]:
        for p in self.planes:
            if p.name == HOST_PLANE:
                return p
        return None


def load(path: Path) -> Trace:
    """Read a trace.  Lines of one name on a plane are merged.  Events'
    stats are left behind: on this runtime they hold device offsets and
    run ids, no scope path (``dump.py`` shows them)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    planes = []
    for plane in data.planes:
        lines: Dict[str, List[Event]] = {}
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for e in line.events:
                start = float(e.start_ns)
                events.append(
                    Event(e.name, start, start + float(e.duration_ns)))
        for events in lines.values():
            events.sort(key=lambda e: (e.start, -e.end))
        planes.append(Plane(plane.name, lines))
    return Trace(planes)
