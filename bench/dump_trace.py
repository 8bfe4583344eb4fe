"""Print what a profiler trace holds, to look at one by hand.

    python bench/dump_trace.py [<logdir>]     # default: .bench_trace

Lists every plane and line with its event count, then for each TPU
plane's ops line the operations by summed time, with the detail the trace
gives for them (this is where kernel names show).
"""
from __future__ import annotations

import glob
import os
import sys


def main(logdir: str = ".bench_trace") -> None:
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                         "*.xplane.pb")))[-1]
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            print(f"  line {line.name!r}: {len(evs)} events")
            if not plane.name.startswith("/device") or not evs:
                continue
            acc: dict[str, list] = {}
            for ev in evs:
                a = acc.setdefault(ev.name, [0, 0, dict(ev.stats)])
                a[0] += 1
                a[1] += ev.duration_ns
            for name, (n, ns, stats) in sorted(acc.items(),
                                               key=lambda kv: -kv[1][1])[:25]:
                detail = {k: str(v)[:160] for k, v in stats.items()}
                print(f"    {ns / 1e9:10.6f}s x{n:<5} {name[:80]} {detail}")


if __name__ == "__main__":
    main(*sys.argv[1:])
