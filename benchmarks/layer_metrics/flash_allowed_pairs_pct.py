"""Layer: Kernels. Of the (query, key) pairs in the tiles the flash kernels
EXECUTE under the block-diffusion mask, the share the mask allows: both
static, from the program's own tile function at the block shape its kernels
pick (``models/transformer.py::block_diffusion_pairs`` ->
``ops/flash_attention.py::block_diffusion_tile_counts``). 80.0 at 8,192
trained positions, blocks of 4 and 1024 x 1024 tiles: the clean diagonal
tiles are half masked, the noised rows' clean diagonal tiles too, and each
noised diagonal tile runs 1,048,576 pairs for 4,096 allowed. Higher is
better: it is what a finer walk of those tiles would gain. A cell whose
configuration has no such mask has nothing to read."""


def read(run):
    pairs = (run["facts"].get("check") or {}).get("flash_pairs")
    if not pairs or not pairs.get("executed_pairs"):
        return None
    return 100.0 * pairs["allowed_pairs"] / pairs["executed_pairs"]
