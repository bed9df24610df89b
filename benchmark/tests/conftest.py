"""Run by hand, on the CPU, from the repo's root:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

Not under ``tests/``: the tier-1 run neither slows nor hangs on them."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))                   # harness, drivers
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))  # paddle_tpu
