"""The benchmark's own tests run on the CPU at tiny sizes: every device
route of the program pinned on (the CPU router would pick the host),
Pallas kernels in interpret mode.  Nothing here is a device number."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
for knob in ("PLAIN", "DICT", "BSS", "DBA", "DELTA"):
    os.environ[f"PARQUET_TPU_{knob}_RUNS"] = "device"
os.environ["PARQUET_TPU_PALLAS"] = "pallas"

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.dirname(BENCH), BENCH):
    sys.path.insert(0, p)
