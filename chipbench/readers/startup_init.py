"""Seconds the program took to build its initial train state
(``run_start.init_s``: ``create_train_state`` until the state is ready)."""


def read(ctx: dict, args: dict):
    init_s = (ctx["telemetry"].get("run_start") or {}).get("init_s")
    return None if init_s is None else float(init_s)
