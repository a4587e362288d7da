#!/usr/bin/env python3
"""Print what an ``.xplane.pb`` holds: planes, lines, event counts, the most
frequent names and the extent of each line.  For looking at a trace by hand
before trusting ``xplane.reduce_trace`` on it."""

from __future__ import annotations

import collections
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import xplane


def main(path: str) -> int:
    print(path, os.path.getsize(path), "bytes")
    with open(path, "rb") as f:
        space = memoryview(f.read())
    for num, _wt, plane_buf in xplane.fields(space):
        if num != 1:
            continue
        name, lines, names = "", [], {}
        for pnum, _pwt, val in xplane.fields(plane_buf):
            if pnum == 2:
                name = bytes(val).decode("utf-8", "replace")
            elif pnum == 3:
                lines.append(val)
            elif pnum == 4:
                for mnum, _mwt, mval in xplane.fields(val):
                    if mnum == 2:
                        ident, mname = xplane._metadata(mval)
                        names[ident] = mname
        print(f"plane {name!r}: {len(lines)} lines, {len(names)} event names")
        for line_buf in lines:
            lname, t0, events = "", 0, []
            for lnum, _lwt, val in xplane.fields(line_buf):
                if lnum == 2:
                    lname = bytes(val).decode("utf-8", "replace")
                elif lnum == 3:
                    t0 = xplane._signed(val)
                elif lnum == 4:
                    events.append(val)
            decoded = [xplane._event(ev, t0, names) for ev in events[:200000]]
            count = collections.Counter(n for _s, _e, n in decoded)
            extent = (min(s for s, _e, _n in decoded), max(e for _s, e, _n in decoded)) if decoded else None
            print(f"  line {lname!r}: timestamp_ns {t0}, {len(events)} events, extent {extent}")
            print("    ", [(n[:60], c) for n, c in count.most_common(6)])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
