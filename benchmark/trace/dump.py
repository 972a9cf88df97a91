"""``python3 -m benchmark.trace.dump <file.xplane.pb> [events per line]``:
what a trace holds — its planes with their stats, their lines with the
names that took most time, and the first events of each line with their
stats.  Look at one trace by hand before trusting the reduction on a new
runtime."""

from __future__ import annotations

import sys
from collections import Counter


def main(argv) -> int:
    from jax.profiler import ProfileData

    shown = int(argv[2]) if len(argv) > 2 else 5
    for plane in ProfileData.from_file(argv[1]).planes:
        print(f"PLANE {plane.name}")
        print(f"  stats {_stats(plane)}")
        for line in plane.lines:
            events = list(line.events)
            top = Counter()
            for e in events:
                top[e.name] += e.duration_ns
            print(f"  LINE {line.name!r}: {len(events)} events")
            for label, ns in top.most_common(shown):
                print(f"    total {ns / 1e6:10.3f} ms  {label[:120]}")
            for e in events[:shown]:
                print(f"    first: {e.name[:100]} start={e.start_ns:.0f} "
                      f"dur={e.duration_ns:.0f} {_stats(e)}")
    return 0


def _stats(thing) -> dict:
    try:
        return {str(k): str(v)[:160] for k, v in thing.stats}
    except Exception as e:  # a stat of a type ProfileData cannot hand out
        return {"unreadable": str(e)}


if __name__ == "__main__":
    sys.exit(main(sys.argv))
