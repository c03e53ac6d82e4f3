"""Traffic: a mix is a data file of parameters
(``chipbench/traffic/<name>.json``) that names its generator;
``generators/<generator>.py`` yields (a) the engine flags that make the
program generate that traffic from ``--seed`` and (b) the same batches,
made here, for the plain reference.  A new mix of a generator that is
there is a data file and no code; a new kind of traffic adds a
generator beside the others.
"""

from __future__ import annotations

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    generator(mix)  # an unknown generator fails here, before any run
    return mix


def generator(mix: dict):
    try:
        return importlib.import_module(
            f"chipbench.generators.{mix['generator']}")
    except ImportError as e:
        raise ValueError(
            f"traffic: unknown generator {mix.get('generator')!r}") from e


def engine_flags(mix: dict) -> list[str]:
    return generator(mix).engine_flags(mix)


def global_batch(mix: dict, chips: int) -> int:
    return mix["per_chip_batch"] * chips


def batches(mix: dict, seed: int, image_size: int, num_classes: int,
            chips: int, steps: int, **kw) -> list:
    """The first ``steps`` global batches of epoch 0 as the program is
    fed them: ``(images on the wire, int32 labels)``."""
    return generator(mix).batches(mix, seed, image_size, num_classes,
                                  chips, steps, **kw)
