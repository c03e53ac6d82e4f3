"""Share of the window's wall that the program's host spans of one name
cover (``--trace steps`` spans, clipped to the window), in percent."""


def seconds_in_window(ctx: dict, name: str) -> float | None:
    spans = [s for s in ctx["spans"] if s.get("n") == name]
    if not ctx["spans"]:
        return None  # the program wrote no spans: nothing to read
    a, b = ctx["t_open"], ctx["t_close"]
    return sum(max(0.0, min(b, s["t1"]) - max(a, s["t0"])) for s in spans)


def read(ctx: dict, args: dict):
    secs = seconds_in_window(ctx, args["span"])
    return None if secs is None else 100.0 * secs / ctx["wall"]
