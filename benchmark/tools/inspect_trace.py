"""Print what a profiler trace holds, to look at one by hand before writing
code against it: planes, lines, how many events, sample names and stats.

    python3 benchmark/tools/inspect_trace.py <log_dir or .xplane.pb> [--cut out.json.gz START_MS LEN_MS]
"""

from __future__ import annotations

import sys
from collections import Counter
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark import trace_reduce  # noqa: E402


def main(argv) -> int:
    from jax.profiler import ProfileData

    path = Path(argv[1])
    if path.is_dir():
        path = trace_reduce.find_xplane(path)
    data = ProfileData.from_file(str(path))
    for plane in data.planes:
        print(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            names = Counter(trace_reduce.parse_op(e.name)[0].split(' ')[0] for e in events)
            print("    names:", names.most_common(12))
            cats = Counter()
            for e in events[:20000]:
                for key, val in e.stats:
                    if key == "hlo_category":
                        cats[str(val)] += 1
            if cats:
                print("    hlo_category:", cats.most_common(20))
            for e in events[:3]:
                print("    e.g.", e.name[:100], e.start_ns, e.duration_ns,
                      [(k, str(v)[:60]) for k, v in e.stats][:14])
    if "--cut" in argv:
        i = argv.index("--cut")
        out, start_ms, len_ms = argv[i + 1], float(argv[i + 2]), float(argv[i + 3])
        trace = trace_reduce.load_xplane(path)
        t0 = trace_reduce.Reduced(trace).window_ns[0] + int(start_ms * 1e6)
        trace_reduce.to_json(trace_reduce.cut(trace, t0, t0 + int(len_ms * 1e6)), out)
        print(f"cut {len_ms} ms from +{start_ms} ms into {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
