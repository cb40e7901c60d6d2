import os

# The benchmark's tests run on the CPU, whatever the machine holds.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
