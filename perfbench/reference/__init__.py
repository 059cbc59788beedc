"""Plain references of the benchmark's configurations: one file a family.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
no kernels, no batching, no cache.  Nothing here imports the program under
test, and nothing here reads what the program has made: the weights come
from a seed, the inputs from the benchmark's own generators.  A
configuration's ``reference`` is the name of its family's file here, and a
new family is a new file: ``load`` finds it by that name.
"""

import os

from perfbench import manifest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(name: str):
    """``perfbench/reference/<name>.py``, the module of one family."""
    return manifest.load_module(BENCH_DIR, "reference", name)
