"""Layer: Kernels. Summed device time of the short convolution's two Mosaic
kernels (``ops/short_conv.py``: ``_short_conv_forward``, twice a layer
under full remat, and ``_short_conv_backward``) per step, by name, on the
first device. A family that does not name them (``Family.kernels`` without
``short_conv``) has nothing to read."""


def read(run):
    trace = run.get("trace")
    seconds = sum((trace or {}).get("kernel_s", {}).get("short_conv", {}).values())
    if not seconds or not trace["steps"]:
        return None
    return seconds / trace["steps"] * 1e3
