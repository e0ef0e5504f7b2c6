import os
import sys

# the benchmark's own checks run on the CPU, apart from the repo's tier-1
# tests: `JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q`
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
