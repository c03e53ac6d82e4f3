"""Device-busy milliseconds per traced step on the fullest device: the
union of the device-op intervals inside the traced steps."""


def read(ctx: dict, args: dict):
    t = ctx["trace"]
    if t is None:
        return None
    busy = t["per_device"][t["fullest"]]["busy_ns"]
    return busy / 1e6 / t["n_steps"]
