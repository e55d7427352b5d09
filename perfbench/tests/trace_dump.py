"""Print the shape of a recorded trace (planes, lines, the events that took
most time): `python -m perfbench.tests.trace_dump <trace dir> [out file]`.
Look at a trace with this before writing a reader against it."""

import collections
import sys

from jax.profiler import ProfileData

from perfbench import trace_reduce


def dump(trace_dir, out=sys.stdout):
    path = trace_reduce.newest_xplane(trace_dir)
    print(f"# {path}", file=out)
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"plane {plane.name!r}", file=out)
        for line in plane.lines:
            events = list(line.events)
            if not events:
                continue
            acc = collections.Counter()
            cnt = collections.Counter()
            for e in events:
                acc[e.name] += e.duration_ns
                cnt[e.name] += 1
            print(f"  line {line.name!r}: {len(events)} events", file=out)
            top = 40 if plane.name.startswith("/device") else 12
            for name, ns in acc.most_common(top):
                print(f"    {ns / 1e6:10.3f} ms {cnt[name]:7d}  {name[:150]}", file=out)


if __name__ == "__main__":
    if len(sys.argv) > 2:
        with open(sys.argv[2], "w") as f:
            dump(sys.argv[1], f)
    else:
        dump(sys.argv[1])
