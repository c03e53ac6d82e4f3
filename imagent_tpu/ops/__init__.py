import jax

from imagent_tpu.ops.cross_entropy import softmax_cross_entropy  # noqa: F401
from imagent_tpu.ops.mixing import make_mix_fn  # noqa: F401


def resolve_interpret(interpret: bool | None) -> bool:
    """Whether a Pallas kernel call runs in the interpreter.

    An explicit bool wins — the kernel tests pass ``True``, the
    chip-compile tests ``False``. ``None`` follows the platform JAX
    initialized: compiled on the TPU; interpreted on the CPU, which
    only tests and rehearsals select, and explicitly
    (``JAX_PLATFORMS=cpu`` / ``--backend=cpu``). The engine refuses a
    ``--backend=tpu`` run that did not get the TPU
    (``cluster.require_backend``), so a chip run cannot reach the
    interpreter this way. Any other platform is refused rather than
    quietly interpreted."""
    if interpret is not None:
        return bool(interpret)
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"Pallas TPU kernel requested on the {backend!r} platform: "
            "it compiles for the TPU and runs interpreted only on the "
            "CPU (tests/rehearsals); pass interpret= explicitly to "
            "override")
    return backend == "cpu"
