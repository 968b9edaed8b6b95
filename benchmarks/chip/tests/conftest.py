"""Put the benchmark's own modules and the program on the path, and keep
the CPU test runs out of the checkout's compile cache (the benchmark's
cache is for the chip)."""
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parents[2] / "src")]

import jax  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)
