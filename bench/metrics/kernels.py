"""Names by which the program's Pallas kernels appear in a device trace.

The kernels carry no ``name=`` of their own; on a v5e chip (JAX 0.9) each
``pallas_call`` shows as a custom call named after the jitted function
that makes it (``%hist_tiles_pallas.72``), as recorded in
``bench/tests/data/sample.xplane.pb``.
"""
HIST = ("hist_tiles_pallas",)                 # kernels/hist_kernel.py
SPLIT = ("split_scan_pallas",)                # kernels/split_kernel.py
TRAVERSE = ("forest_traverse_pallas",)        # kernels/predict_kernel.py
PALLAS = HIST + SPLIT + TRAVERSE + ("pallas_call",)
