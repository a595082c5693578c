"""Writes what a trace holds, for reading by hand: planes, lines, and per
line the names that took most time with a sample of their stats. Used once
per runtime to write the kernel patterns into the metrics' files."""
from __future__ import annotations

import json
import os


def dump(xplane_path: str, summary, out_dir: str) -> None:
    from jax.profiler import ProfileData
    os.makedirs(out_dir, exist_ok=True)
    if os.environ.get("BENCH_TRACE_KEEP"):
        import gzip
        import shutil
        with open(xplane_path, "rb") as src, gzip.open(
                os.path.join(out_dir, "trace.xplane.pb.gz"), "wb", 6) as dst:
            shutil.copyfileobj(src, dst)
    data = ProfileData.from_file(xplane_path)
    lines = []
    for plane in data.planes:
        lines.append(f"PLANE {plane.name}")
        for line in plane.lines:
            by, n, sample = {}, 0, {}
            for e in line.events:
                n += 1
                by[e.name] = by.get(e.name, 0.0) + e.duration_ns
                if e.name not in sample and len(sample) < 400:
                    sample[e.name] = [(k, str(v)[:120])
                                      for k, v in list(e.stats)[:12]]
            lines.append(f"  LINE {line.name!r}: {n} events, "
                         f"{len(by)} names")
            for name, ns in sorted(by.items(), key=lambda kv: -kv[1])[:60]:
                lines.append(f"    {ns / 1e6:10.3f} ms  {name[:100]}  "
                             f"{sample.get(name, '')}")
    with open(os.path.join(out_dir, "structure.txt"), "w") as f:
        f.write("\n".join(lines))
    ops = {}
    for ev in summary.devices.values():
        for name, _, _d, own in ev:
            o = ops.setdefault(name, [0.0, 0])
            o[0] += own / 1e6
            o[1] += 1
    mods = {}
    for ev in summary.modules.values():
        for name, _, d in ev:
            o = mods.setdefault(name, [0.0, 0])
            o[0] += d / 1e6
            o[1] += 1
    with open(os.path.join(out_dir, "ops.json"), "w") as f:
        json.dump({"ops_ms_count": {k[:600]: v for k, v in sorted(
            ops.items(), key=lambda kv: -kv[1][0])[:3000]},
            "modules_ms_count": mods}, f, indent=0)
    with open(os.path.join(out_dir, "summary.json"), "w") as f:
        json.dump({"window_s": summary.window_s, "busy_s": summary.busy_s,
                   "breakdown": summary.breakdown(40),
                   "host_names": sorted({h[0] for h in summary.host})},
                  f, indent=1)
