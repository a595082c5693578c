"""Programs the device ran under no name of the package's table, per
model launch: runs on the `XLA Modules` line that start in the recorded
part of the window and whose name is not `jit_<one of named>`, over the
runs of `launches` there (where the trace has none of those, a commit
that does not name its programs, over the host spans `launch_spans`).
What it counts is what a launch sends to the device beside its program:
eager one-operation programs (`jit__threefry_seed`, `jit_concatenate`),
a helper nobody named. The names found go to standard error."""
import sys

from benchmarks.readers.program_device import module_events, recorded, runs


def read(ctx, spec):
    rec = recorded(ctx)
    if rec is None:
        return None
    tr = ctx["trace"]
    named = {f"jit_{p}" for p in spec["named"]}
    found = {}
    for a, _, name in module_events(ctx):
        if rec[0] <= a < rec[1] and name not in named:
            found[name] = found.get(name, 0) + 1
    launches = len(runs(ctx, spec["launches"])) or sum(
        rec[0] <= s < rec[1] for n, s, _ in tr.host
        if n in spec["launch_spans"])
    if not launches:
        return None
    print(f"programs under no name of the table, over {launches} launches: "
          f"{dict(sorted(found.items(), key=lambda kv: -kv[1]))}",
          file=sys.stderr, flush=True)
    return sum(found.values()) / launches
