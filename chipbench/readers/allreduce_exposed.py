"""Collective milliseconds per traced step during which no compute op
runs on that device (the worst device)."""


def read(ctx: dict, args: dict):
    t = ctx["trace"]
    if t is None:
        return None
    per = t["per_device"].values()
    if not any(p["collective_ns"] for p in per):
        return None  # no collective in this trace
    return max(p["exposed_collective_ns"] for p in per) / 1e6 / t["n_steps"]
