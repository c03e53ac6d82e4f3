"""Seconds the program spent compiling or loading its step executables
(``run_start.compile_cache``: ``compile_s`` + ``load_s``)."""


def read(ctx: dict, args: dict):
    start = ctx["telemetry"].get("run_start") or {}
    cc = start.get("compile_cache")
    if not cc:
        return None
    return float(cc.get("compile_s", 0.0)) + float(cc.get("load_s", 0.0))
