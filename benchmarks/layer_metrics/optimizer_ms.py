"""Layer: Step. Device time per step of ops under scope ``optimizer``
(``train/jax_utils.py::build_sharded_train_step.apply_update``: the
optimizer's update and the parameter add), on the first device."""
from benchmarks.harness import scopes


def read(run):
    return scopes.block_ms(run, "optimizer")
