"""One counter over another (`num`, `den` in the file), times the number of
routed experts the configuration holds to the power `held_power` (-1: per
held expert; 1: against the mean over them; 0 or absent: as it is)."""


def read(ctx, spec):
    c = ctx["rec"].counters
    if not c.get(spec["den"]) or spec["num"] not in c:
        return None
    held = float(ctx["cell"].config.get("n_routed_experts", 1))
    return held ** int(spec.get("held_power", 0)) \
        * c[spec["num"]] / c[spec["den"]]
