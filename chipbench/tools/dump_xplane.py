"""Look at one trace by hand: planes, lines, event counts and the first
events of each line with their stats.

    python3 chipbench/tools/dump_xplane.py <file.xplane.pb> [events per line]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from chipbench import trace_reader as tr  # noqa: E402


def main() -> None:
    n = int(sys.argv[2]) if len(sys.argv) > 2 else 6
    for plane in tr.load(sys.argv[1]):
        print(f"PLANE {plane['name']!r}: {len(plane['lines'])} line(s)")
        for line in plane["lines"]:
            evs = line["events"]
            print(f"  LINE {line['name']!r}: {len(evs)} event(s)")
            for ev in evs[:n]:
                print(f"    {ev['name'][:90]!r} start {ev['start']} "
                      f"dur {ev['end'] - ev['start']} stats "
                      f"{ {k: str(v)[:60] for k, v in ev['stats'].items()} }")


if __name__ == "__main__":
    main()
