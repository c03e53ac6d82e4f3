"""Drive one --rehearsal run of the benchmark with the program's timed
path broken underneath (tests only):

    python tests/chipbench/broken_run.py <fault> -- <run.py arguments>

``state_unchanged``  the step returns its state as it got it;
``half_batch``       half of each chip's rows left out, the mean taken
                     over the rest (the first half stands in twice);
``no_exchange``      the gradient exchange between chips left out;
``none``             nothing broken (the sound twin of the above).

The persistent compile cache is off, so that a broken step is never
stored where a sound run could load it.  A cell that is kept as files
but is not in ``BENCHMARK.json`` yet (``r50_b256x4_data4``) is driven
through a manifest entry made here, in memory, from its cell file.
"""

import os
import sys

os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def plant(fault: str) -> None:
    import jax
    import jax.numpy as jnp
    from imagent_tpu import engine, train

    if fault == "no_exchange":
        real_pmean = train.pmean_tree

        def pmean(tree, axis_name):
            if axis_name == train.DATA_AXIS:
                return tree
            return real_pmean(tree, axis_name)

        train.pmean_tree = pmean
        return
    real_make = train.make_train_step

    def make(*args, **kwargs):
        real = real_make(*args, **kwargs)

        def broken(state, images, labels, lr):
            if fault == "half_batch":
                h = images.shape[0] // 2
                images = jnp.concatenate([images[:h], images[:h]])
                labels = jnp.concatenate([labels[:h], labels[:h]])
            new_state, metrics = real(state, images, labels, lr)
            if fault == "state_unchanged":
                new_state = state.replace(step=new_state.step)
            return new_state, metrics

        return jax.jit(broken, donate_argnums=(0,))

    engine.make_train_step = make


def list_cell(run, name: str) -> None:
    """Make ``run`` read a manifest that lists the cell ``name``."""
    real = run.load_json

    def load_json(path):
        body = real(path)
        if (os.path.basename(path) == "BENCHMARK.json" and name not in
                [w["name"] for w in body["workloads"]]):
            cell = real(os.path.join(ROOT, "chipbench", "workloads",
                                     f"{name}.json"))
            body["workloads"].append({k: cell[k] for k in (
                "name", "config", "traffic", "chips", "why")})
        return body

    run.load_json = load_json


def main() -> int:
    fault = sys.argv[1]
    assert sys.argv[2] == "--", sys.argv
    if fault != "none":
        plant(fault)
    from chipbench import run
    args = sys.argv[3:]
    list_cell(run, args[args.index("--workload") + 1])
    return run.main(args + ["--rehearsal"])


if __name__ == "__main__":
    sys.exit(main())
