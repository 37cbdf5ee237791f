#!/usr/bin/env python3
"""Look at one trace by hand: the planes, their lines, and the raw names of
the events that match a pattern, with their count and total time.

    BENCH_KEEP_TRACE=1 python3 benchmarks/run.py --workload <cell> ... --trace 1
    python3 benchmarks/tools/trace_names.py .bench_trace/<cell> custom-call
"""

import glob
import os
import sys


def main():
    log_dir, pattern = sys.argv[1], (sys.argv[2] if len(sys.argv) > 2 else "")
    import jax

    path = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                         "*.xplane.pb")),
                  key=os.path.getmtime)[-1]
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        for line in plane.lines:
            seen = {}
            n = 0
            for e in line.events:
                n += 1
                if pattern and pattern in e.name:
                    c = seen.setdefault(e.name, [0, 0])
                    c[0] += 1
                    c[1] += e.duration_ns
            print(f"{plane.name} | {line.name}: {n} events")
            for name, (count, ns) in sorted(seen.items(),
                                            key=lambda t: -t[1][1])[:12]:
                print(f"    {count} x, {ns * 1e-9:.6f} s: {name[:700]}")


if __name__ == "__main__":
    main()
