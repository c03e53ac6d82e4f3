"""Set-up that is neither compile/load nor warm-up steps: process start
to the first poll, less the compile/load seconds."""

from chipbench.readers import startup_compile_load


def read(ctx: dict, args: dict):
    cl = startup_compile_load.read(ctx, args)
    if cl is None:
        return None
    return ctx["clock"].all_stamps[0] - ctx["t0"] - cl
