import os
import sys

# smoke tests and benches must see the REAL device count (1 CPU device) —
# the 512-device XLA flag is set ONLY inside launch/dryrun.py.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
# tests run on the CPU (Pallas kernels interpreted) even where a TPU is
# attached; the chip is exercised by chip_smoke.py, one process at a time
os.environ["JAX_PLATFORMS"] = "cpu"
