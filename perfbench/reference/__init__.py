"""Plain references of the benchmark's configurations.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
no kernels, no batching, no cache.  Nothing here imports the program under
test, and nothing here reads what the program has made: the weights come
from ``perfbench/weights.py`` (a seed), the pixels from the benchmark's own
pictures.  ``FAMILIES`` maps a configuration's ``family`` to its forward.
"""

from perfbench.reference import efficientnet, xception

FAMILIES = {
    "efficientnet": efficientnet.forward,
    "xception": xception.forward,
}
