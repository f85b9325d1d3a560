"""Layer: Model. Device time per step of ops under scope ``head``
(``models/transformer.py::_head``: final norm, logits, f32 cast) or
``loss`` (``logits_loss``), all phases, on the first device. One metric
for both: XLA fuses the logits' epilogue into the loss, and a fusion
carries one name."""
from benchmarks.harness import scopes


def read(run):
    return scopes.block_ms(run, "head", "loss")
