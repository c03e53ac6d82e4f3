"""The ``startup.init_s`` reader: the program's ``run_start.init_s``
where the record carries it, nothing where it does not."""

from chipbench.readers import startup_init


def test_reads_init_s_from_run_start():
    ctx = {"telemetry": {"run_start": {"init_s": 2.75,
                                       "compile_cache": {"load_s": 2.0}}}}
    assert startup_init.read(ctx, {}) == 2.75


def test_none_without_the_counter():
    # A program that does not record the counter (run_start without
    # init_s), or a run that wrote no run_start at all.
    assert startup_init.read(
        {"telemetry": {"run_start": {"compile_cache": None}}}, {}) is None
    assert startup_init.read({"telemetry": {"run_start": None}}, {}) is None
    assert startup_init.read({"telemetry": {}}, {}) is None
