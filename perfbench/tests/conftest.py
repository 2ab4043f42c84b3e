import os

# the harness's tests run on the CPU at tiny sizes; the chip is for runs
os.environ.setdefault("JAX_PLATFORMS", "cpu")
