"""The persistent compile cache is for chip runs; the tests compile afresh
on the CPU.  The CPU shows four devices, as a four-chip host has, so that
a cell over a mesh runs its own layout here; set before JAX starts."""
import os

os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
