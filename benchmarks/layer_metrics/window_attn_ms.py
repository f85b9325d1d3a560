"""Layer: Model. Device time per step of ops under scope ``window_attention``
(``models/transformer.py::_window_mixer``: a window layer's whole mixer, the
q / k / v projections, RoPE, the seven-fold repeat of K and V, the flash
kernels under the window and ``W_o``), forward, remat's recompute and
backward, on the first device. Inside ``attention_ms``; ``window_flash_ms``
is inside it. A program without the scope has nothing to read."""
from benchmarks.harness import named_scope


def read(run):
    return named_scope.scope_ms(run, "window_attention")
