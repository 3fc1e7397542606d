"""Print what a profile holds, for reading a trace by hand.

    python3 benchmarks/chip/inspect_trace.py <dir with the .xplane.pb>

For every plane and line: its name, the number of events, and the event
names with the most time; for device planes, the stats of a few events.
This is what ``trace.py`` keys on (``PERF.md`` records what was found).
"""
import sys

import jax

sys.path.insert(0, str(__import__("pathlib").Path(__file__).resolve()
                       .parents[2]))

from benchmarks.chip.trace import xplane_file  # noqa: E402


def main(log_dir: str, top: int = 12) -> None:
    profile = jax.profiler.ProfileData.from_file(xplane_file(log_dir))
    for plane in profile.planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            tot: dict = {}
            for e in events:
                tot[e.name] = tot.get(e.name, 0.0) + e.duration_ns
            print(f"  LINE {line.name!r}: {len(events)} events, "
                  f"{len(tot)} names, {sum(tot.values()) / 1e9:.6f} s")
            for name, ns in sorted(tot.items(), key=lambda x: -x[1])[:top]:
                print(f"    {ns / 1e9:12.6f} s  {name[:150]}")
            if plane.name.startswith("/device:"):
                for e in events[:3]:
                    print(f"    stats of {e.name[:60]!r}: "
                          f"{[(k, str(v)[:80]) for k, v in e.stats][:12]}")


if __name__ == "__main__":
    main(sys.argv[1])
