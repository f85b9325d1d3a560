"""Layer: Kernels. Device time per step of ops under scope ``window_flash``
(``models/transformer.py::_window_mixer``: the flash kernels called with a
window: forward, dq and dkv of every window layer; they are the global
layers' jitted functions, so the scope and not the kernel's name tells them
apart), on the first device. Inside ``flash_ms`` and ``window_attn_ms``. A
program without the scope has nothing to read."""
from benchmarks.harness import named_scope


def read(run):
    return named_scope.scope_ms(run, "window_flash")
