"""Layer: Device. The bytes one step's selective scans keep from their forward
for their backward (the outputs in the model's dtype and the chunk-start states
in float32, every Mamba-1 layer), by the op's own count
(``ops/selective_scan.py::kept_bytes`` through ``models/transformer.py::
selective_scan_bytes``), as the family's check reports it. A cell whose
configuration has no Mamba-1 layer has nothing to read."""


def read(run):
    return (run["facts"].get("check") or {}).get("scan_kept_gib")
