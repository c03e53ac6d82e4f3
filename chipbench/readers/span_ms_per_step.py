"""Milliseconds per window step that the program's host spans of one
name take (clipped to the window)."""

from chipbench.readers.span_share import seconds_in_window


def read(ctx: dict, args: dict):
    secs = seconds_in_window(ctx, args["span"])
    return None if secs is None else 1e3 * secs / ctx["steps"]
