"""The benchmark's own tests run on the CPU: pin the platform before JAX starts."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
