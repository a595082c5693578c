"""100 x the own device time of the operations whose OWN instruction name
(left of ` = `) holds one of `contains`, over the device's busy time.
With `.remat` it is what XLA's rematerialisation costs a step: the
compiler marks an instruction it computes a second time to save memory
(`%fusion.112.remat`), and nothing else shows it. No such operation
reads 0; no device time gives nothing."""


def read(ctx, spec):
    tr = ctx["trace"]
    if tr is None or not tr.devices or not tr.busy_s:
        return None
    parts = tuple(spec["contains"])
    seconds = sum(sec for name, sec in tr.op_seconds().items()
                  if any(p in name.partition(" = ")[0] for p in parts))
    return 100.0 * seconds / tr.busy_s
