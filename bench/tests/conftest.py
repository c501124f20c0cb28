"""The persistent compile cache is for chip runs; the tests compile afresh
on the CPU."""
import os

os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
