import os

# the benchmark's CPU tests never touch an accelerator
os.environ.setdefault("JAX_PLATFORMS", "cpu")
